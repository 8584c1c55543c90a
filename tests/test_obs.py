"""Telemetry layer tests: registry semantics, Prometheus exposition,
latency sketches (moments + log-histogram agreement), the device
counter block, per-route API latency, the profiler endpoint, and
collector ingest-step self-tracing."""

import json
import math
import threading

import numpy as np
import pytest

from zipkin_tpu import obs
from zipkin_tpu.api import ApiServer
from zipkin_tpu.ingest.collector import Collector
from zipkin_tpu.models.span import Annotation, Endpoint, Span
from zipkin_tpu.query.service import QueryService
from zipkin_tpu.store.memory import InMemorySpanStore

EP = Endpoint(0x01010101, 80, "svc")


def span(tid, sid=1, ts=100):
    return Span(tid, "op", sid, None, (
        Annotation(ts, "sr", EP), Annotation(ts + 10, "ss", EP),
    ), ())


class TestRegistry:
    def test_counter_monotonic_and_locked(self):
        r = obs.Registry()
        c = r.register(obs.Counter("t_total", "h"))
        threads = [
            threading.Thread(target=lambda: [c.inc() for _ in range(1000)])
            for _ in range(8)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value == 8000
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_gauge_fn_and_set(self):
        g = obs.Gauge("g", "h", fn=lambda: 41)
        assert g.value == 41
        g.set(5)
        assert g.value == 5

    def test_reregister_replaces(self):
        r = obs.Registry()
        r.register(obs.Counter("x", "h")).inc(3)
        c2 = r.register(obs.Counter("x", "h"))
        assert r.get("x") is c2 and c2.value == 0

    def test_labels_children(self):
        c = obs.Counter("req_total", "h", labelnames=("route",))
        c.labels(route="/a").inc(2)
        c.labels(route="/b").inc()
        assert c.labels(route="/a").value == 2
        with pytest.raises(ValueError):
            c.labels(nope="x")

    def test_sketch_quantiles_and_moments(self):
        h = obs.LatencySketch("lat_seconds", "h")
        vals = np.random.default_rng(7).uniform(1e-4, 1.0, 5000)
        for v in vals:
            h.observe(float(v))
        p50, p99 = h.quantile_values((0.5, 0.99))
        # DDSketch relative-accuracy guarantee (alpha=1%, small slack
        # for the discrete rank step).
        assert abs(p50 - np.quantile(vals, 0.5)) / p50 < 0.05
        assert abs(p99 - np.quantile(vals, 0.99)) / p99 < 0.05
        snap = h.snapshot()
        assert snap["count"] == 5000
        assert abs(snap["mean"] - vals.mean()) < 1e-6
        assert abs(snap["stddev"] - vals.std()) < 1e-6

    def test_sketch_merge(self):
        a = obs.LatencySketch("m", "h")
        b = obs.LatencySketch("m", "h")
        for v in (0.1, 0.2):
            a.observe(v)
        for v in (0.3, 0.4):
            b.observe(v)
        a.merge(b)
        assert a.count == 4
        assert abs(a.snapshot()["mean"] - 0.25) < 1e-9


class TestPrometheusText:
    def _registry(self):
        r = obs.Registry()
        r.register(obs.Counter("z_total", "a counter")).inc(2)
        r.register(obs.Gauge("z_gauge", "a gauge", fn=lambda: 1.5))
        h = r.register(obs.LatencySketch("z_seconds", "a summary"))
        h.observe(0.25)
        return r

    def test_type_and_help_lines(self):
        text = self._registry().render_text()
        assert "# TYPE z_total counter\n" in text
        assert "# TYPE z_gauge gauge\n" in text
        assert "# TYPE z_seconds summary\n" in text
        assert "# HELP z_total a counter\n" in text
        assert "\nz_total 2\n" in text
        assert "\nz_gauge 1.5\n" in text
        assert 'z_seconds{quantile="0.5"}' in text
        assert 'z_seconds{quantile="0.99"}' in text
        assert "\nz_seconds_count 1\n" in text

    def test_label_escaping(self):
        r = obs.Registry()
        c = r.register(obs.Counter("esc_total", "h",
                                   labelnames=("route",)))
        c.labels(route='we"ird\\path\nx').inc()
        text = r.render_text()
        assert 'esc_total{route="we\\"ird\\\\path\\nx"} 1' in text

    def test_empty_sketch_renders_nan(self):
        r = obs.Registry()
        r.register(obs.LatencySketch("never_seconds", "h"))
        text = r.render_text()
        assert 'never_seconds{quantile="0.5"} NaN' in text
        assert "never_seconds_count 0" in text


class TestFleetExposition:
    """Federated-scrape exposition (obs/fleet.render_federated):
    format validity of the merged ``/metrics?fleet=1`` view — one
    HELP/TYPE per family, follower-name label escaping, and
    counter monotonicity across successive federated scrapes."""

    def _sources(self, follower="r1", inc=3):
        from zipkin_tpu.obs.fleet import registry_snapshot

        a = obs.Registry()
        a.register(obs.Counter("fx_total", "fleet requests")).inc(inc)
        sk = a.register(obs.LatencySketch("fx_seconds", "fleet lat"))
        sk.observe(0.01)
        b = obs.Registry()
        b.register(obs.Counter("fx_total", "fleet requests")).inc(inc)
        return a, b, [
            ((("role", "primary"),), registry_snapshot(a)),
            ((("role", "follower"), ("follower", follower)),
             registry_snapshot(b)),
        ]

    def test_merged_scrape_type_help_unique(self):
        from zipkin_tpu.obs.fleet import render_federated

        _a, _b, sources = self._sources()
        text = render_federated(sources)
        for fam in ("fx_total", "fx_seconds"):
            assert text.count(f"# TYPE {fam} ") == 1, fam
            assert text.count(f"# HELP {fam} ") == 1, fam
        # Both processes' samples survive under the one family header.
        assert text.count("fx_total{") == 2

    def test_follower_name_label_escaping(self):
        from zipkin_tpu.obs.fleet import render_federated

        _a, _b, sources = self._sources(follower='we"ird\\host\nx')
        text = render_federated(sources)
        assert 'follower="we\\"ird\\\\host\\nx"' in text
        # No raw newline may leak into a sample line.
        for line in text.splitlines():
            if line.startswith("fx_total{"):
                assert line.count("}") == 1

    def test_counters_monotonic_across_federated_scrapes(self):
        from zipkin_tpu.obs.fleet import (
            registry_snapshot,
            render_federated,
        )

        a, b, sources = self._sources()

        def scrape():
            srcs = [
                ((("role", "primary"),), registry_snapshot(a)),
                ((("role", "follower"), ("follower", "r1")),
                 registry_snapshot(b)),
            ]
            out = {}
            for line in render_federated(srcs).splitlines():
                if line.startswith("fx_total{"):
                    key, v = line.rsplit(" ", 1)
                    out[key] = float(v)
            return out

        s1 = scrape()
        a.get("fx_total").inc(2)
        b.get("fx_total").inc(5)
        s2 = scrape()
        assert set(s1) == set(s2) and len(s1) == 2
        for key in s1:
            assert s2[key] >= s1[key], key
        assert sum(s2.values()) == sum(s1.values()) + 7

    def test_federated_values_bitwise_match_own_scrape(self):
        """Every sample value in the merged view formats EXACTLY as
        the owning process's own /metrics scrape does (same _fmt
        path) — federation may relabel, never re-round."""
        from zipkin_tpu.obs.fleet import (
            registry_snapshot,
            render_federated,
        )

        r = obs.Registry()
        sk = r.register(obs.LatencySketch("bw_seconds", "h"))
        for v in (0.000123, 0.37, 1.5e-5):
            sk.observe(v)
        def keyed(text):
            out = set()
            for line in text.splitlines():
                if not line or line.startswith("#"):
                    continue
                name = line.split("{")[0].split(" ")[0]
                out.add(name + "|" + line.rsplit(" ", 1)[1])
            return out

        own = keyed(r.render_text())
        fed = keyed(render_federated(
            [((("role", "primary"),), registry_snapshot(r))]))
        assert own == fed


class TestWalTelemetry:
    """The write-ahead log's metric surface (zipkin_tpu.wal): append/
    fsync sketches, segment-bytes and truncation-backlog gauges, and
    the record/replay/corrupt/truncation counters, all rendered in
    Prometheus exposition form."""

    def test_wal_metric_families_exposed(self, tmp_path):
        from zipkin_tpu.wal import WriteAheadLog

        r = obs.Registry()
        wal = WriteAheadLog(str(tmp_path / "w"), fsync="batch",
                            registry=r, compress=False)
        wal.append(b"x" * 200)
        wal.append(b"y" * 200)
        text = r.render_text()
        assert "# TYPE zipkin_wal_append_seconds summary" in text
        assert "# TYPE zipkin_wal_fsync_seconds summary" in text
        assert "# TYPE zipkin_wal_segment_bytes gauge" in text
        assert ("# TYPE zipkin_wal_truncation_backlog_segments gauge"
                in text)
        assert "# TYPE zipkin_wal_records_total counter" in text
        assert "# TYPE zipkin_wal_replayed_records_total counter" in text
        assert "# TYPE zipkin_wal_corrupt_records_total counter" in text
        assert "# TYPE zipkin_wal_truncated_segments_total counter" in text
        assert "\nzipkin_wal_records_total 2\n" in text
        assert "zipkin_wal_append_seconds_count 2" in text
        # fsync=batch observes one fsync per append
        assert "zipkin_wal_fsync_seconds_count 2" in text
        vals = r.as_dict()
        assert vals["zipkin_wal_segment_bytes"] > 0
        assert vals["zipkin_wal_truncation_backlog_segments"] == 1.0
        wal.close()
        # close() unregisters this log's metrics
        assert r.get("zipkin_wal_records_total") is None

    def test_corrupt_and_truncated_counters(self, tmp_path):
        from zipkin_tpu.wal import WriteAheadLog

        d = str(tmp_path / "w")
        wal = WriteAheadLog(d, fsync="batch", compress=False,
                            segment_bytes=1 << 12)
        import os

        for i in range(12):
            wal.append(bytes([i]) * 1500)
        removed = wal.truncate(upto_seq=8)
        assert removed >= 1
        assert int(wal.c_truncated.value) == removed
        wal.close()
        # tear the tail, reopen with a fresh registry: the open-time
        # scan counts the cut record
        seg = sorted(n for n in os.listdir(d) if n.endswith(".seg"))[-1]
        with open(os.path.join(d, seg), "r+b") as f:
            f.truncate(os.path.getsize(os.path.join(d, seg)) - 10)
        r2 = obs.Registry()
        wal2 = WriteAheadLog(d, fsync="batch", registry=r2)
        text = r2.render_text()
        assert "\nzipkin_wal_corrupt_records_total 1\n" in text
        wal2.close()


class TestWindowTelemetry:
    """The windowed Moments-sketch arena's metric surface
    (zipkin_window_*): fold counters (monotonic across scrapes and
    ring self-clears), the cell-occupancy/retention gauges, and the
    per-endpoint serve-latency sketch family, all in Prometheus
    exposition form with TYPE/HELP lines and escaped labels."""

    BASE_US = 1_700_000_000_000_000

    def _store(self, reg):
        from zipkin_tpu.store.device import StoreConfig
        from zipkin_tpu.store.tpu import TpuSpanStore

        return TpuSpanStore(StoreConfig(
            capacity=1 << 10, ann_capacity=1 << 12,
            bann_capacity=1 << 11, max_services=16, max_span_names=32,
            max_annotation_values=64, max_binary_keys=16,
            cms_width=1 << 10, hll_p=8, quantile_buckets=512,
            window_seconds=60, window_buckets=4,
        ), registry=reg)

    def _spans(self, n, errors=0, base_off=0):
        out = []
        for i in range(n):
            ts = self.BASE_US + base_off + i
            anns = [Annotation(ts, "sr", EP),
                    Annotation(ts + 500, "ss", EP)]
            if i < errors:
                anns.append(Annotation(ts + 1, "error", EP))
            out.append(Span(i + 1, "op", i + 1, None, tuple(anns), ()))
        return out

    def test_window_families_exposed_and_monotonic(self):
        reg = obs.Registry()
        store = self._store(reg)
        store.apply(self._spans(10, errors=3))
        text = reg.render_text()
        assert "# TYPE zipkin_window_spans_total counter" in text
        assert "# HELP zipkin_window_spans_total" in text
        assert "# TYPE zipkin_window_errors_total counter" in text
        assert "# TYPE zipkin_window_cells_active gauge" in text
        assert "# TYPE zipkin_window_retention_seconds gauge" in text
        assert "\nzipkin_window_spans_total 10\n" in text
        assert "\nzipkin_window_errors_total 3\n" in text
        assert "\nzipkin_window_cells_active 1\n" in text
        assert "\nzipkin_window_retention_seconds 240\n" in text
        # Monotonic across scrapes even when the ring SELF-CLEARS a
        # slot (bucket 0 overwritten 4 ring-lengths later): the cell
        # gauge may move, the fold counters only climb.
        v1 = reg.as_dict()
        store.apply(self._spans(
            5, errors=1, base_off=4 * 60_000_000))
        v2 = reg.as_dict()
        assert v2["zipkin_window_spans_total"] == 15
        assert v2["zipkin_window_errors_total"] == 4
        assert (v2["zipkin_window_spans_total"]
                >= v1["zipkin_window_spans_total"])
        assert (v2["zipkin_window_errors_total"]
                >= v1["zipkin_window_errors_total"])
        # counters() surfaces the same accounting for /metrics JSON.
        c = store.counters()
        assert c["window_spans"] == 15.0
        assert c["window_errors"] == 4.0

    def test_window_query_sketch_family_and_escaping(self):
        from zipkin_tpu.query.engine import QueryEngine

        reg = obs.Registry()
        store = self._store(reg)
        store.apply(self._spans(8))
        eng = QueryEngine(store, registry=reg)
        try:
            eng.windowed_quantiles("svc", [0.5])
            eng.slo_burn("svc")
            text = reg.render_text()
            assert ("# TYPE zipkin_window_query_seconds summary"
                    in text)
            assert ('zipkin_window_query_seconds{'
                    'endpoint="windowed_quantiles",quantile="0.5"}'
                    in text)
            assert ('zipkin_window_query_seconds{endpoint="slo_burn"'
                    in text)
            assert "zipkin_window_query_seconds_count" in text
        finally:
            eng.close()
        # Label escaping holds for the family machinery the window
        # sketch uses (hostile endpoint names can't corrupt the feed).
        s = obs.LatencySketch("w_seconds", "h",
                              labelnames=("endpoint",))
        s.labels(endpoint='a"b\\c\nd').observe(0.1)
        r2 = obs.Registry()
        r2.register(s)
        assert 'endpoint="a\\"b\\\\c\\nd"' in r2.render_text()


class TestApiMetricsSurface:
    """Acceptance shape: /metrics serves valid Prometheus text covering
    every pipeline stage with latency quantiles, and stays monotonic
    across scrapes."""

    def _app(self):
        reg = obs.Registry()
        store = InMemorySpanStore()
        collector = Collector(store, concurrency=2, registry=reg)
        api = ApiServer(QueryService(store), collector, registry=reg)
        return store, collector, api, reg

    def test_all_five_stages_present(self):
        store, collector, api, reg = self._app()
        collector.accept([span(1)])
        collector.flush()
        api.handle("GET", "/api/services", {})
        status, payload = api.handle("GET", "/metrics", {})
        assert status == 200
        text = payload.body.decode()
        stage_markers = {
            "queue": "zipkin_queue_depth",
            "collector": "zipkin_collector_spans_stored_total",
            "store": 'zipkin_store_counter{name="spans_stored"}',
            "query": 'zipkin_api_request_seconds{route="/api/services"'
                     ',quantile="0.99"}',
            "sampler": "zipkin_sampler_rate",
        }
        for stage, marker in stage_markers.items():
            assert marker in text, (stage, text)
        # >= 12 distinct metric families exposed.
        families = {
            line.split()[2] for line in text.splitlines()
            if line.startswith("# TYPE")
        }
        assert len(families) >= 12, sorted(families)
        # p50 AND p99 lines exist for the latency summaries.
        assert 'quantile="0.5"' in text and 'quantile="0.99"' in text

    def test_counters_monotonic_across_requests(self):
        store, collector, api, reg = self._app()

        def scrape():
            _, payload = api.handle("GET", "/metrics", {})
            out = {}
            for line in payload.body.decode().splitlines():
                if line.startswith("#"):
                    continue
                k, _, v = line.rpartition(" ")
                if v not in ("NaN", "+Inf", "-Inf"):
                    out[k] = float(v)
            return out

        first = scrape()
        for i in range(3):
            collector.accept([span(10 + i)])
        collector.flush()
        api.handle("GET", "/api/services", {})
        second = scrape()
        counters = [
            k for k in first
            if k.endswith("_total") or k.endswith("_count")
        ]
        assert counters
        for k in counters:
            assert second.get(k, 0) >= first[k], k
        assert (second["zipkin_collector_spans_stored_total"]
                >= first["zipkin_collector_spans_stored_total"] + 3)

    def test_json_form_unchanged(self):
        store, collector, api, reg = self._app()
        status, body = api.handle("GET", "/metrics", {"format": "json"})
        assert status == 200
        assert "collector.queue_size" in body
        json.dumps(body)  # still a plain JSON dict

    def test_route_label_normalization(self):
        from zipkin_tpu.api.server import _route_label

        assert _route_label("/api/trace/deadbeef") == "/api/trace/{id}"
        assert _route_label("/api/pin/1f/true") == "/api/pin/{id}"
        assert _route_label("/api/query") == "/api/query"
        assert _route_label("/some/scanner/path") == "other"

    def test_profile_endpoint(self):
        store, collector, api, reg = self._app()
        status, body = api.handle("POST", "/debug/profile",
                                  {"seconds": "0.05"})
        # 200 with a trace dir when the backend can trace, 503 when the
        # profiler is unavailable in this environment — never a crash.
        assert status in (200, 503), body
        if status == 200:
            import os

            assert os.path.isdir(body["profileDir"])
        status2, body2 = api.handle("POST", "/debug/profile",
                                    {"seconds": "nope"})
        assert status2 == 400


class TestCollectorTelemetry:
    def test_threaded_failure_counters_exact(self):
        """Failure-path counters must not lose increments under
        concurrent submitters (the old dict read-modify-write hazard)."""
        reg = obs.Registry()
        store = InMemorySpanStore()
        collector = Collector(store, concurrency=4, registry=reg)
        n_threads, n_each = 8, 50

        def slam():
            for _ in range(n_each):
                collector._decode_segments_slow([b"\x00garbage"])

        threads = [threading.Thread(target=slam) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert collector.bad_payloads == n_threads * n_each

    def test_batch_and_latency_sketches_fill(self):
        reg = obs.Registry()
        store = InMemorySpanStore()
        collector = Collector(store, concurrency=1, registry=reg)
        for i in range(4):
            collector.accept([span(i + 1), span(i + 1, sid=2)])
        collector.flush()
        d = reg.as_dict()
        assert d["zipkin_collector_batch_spans_count"] == 4
        assert d['zipkin_collector_batch_spans{quantile="0.5"}'] == \
            pytest.approx(2.0, rel=0.05)
        assert d["zipkin_collector_write_seconds_count"] == 4

    def test_ingest_self_trace_spans_reach_store(self):
        """self_trace=True records one zipkin-tpu span per ingest step,
        written straight to the store — and never recursively."""
        reg = obs.Registry()
        store = InMemorySpanStore()
        collector = Collector(store, concurrency=1, registry=reg,
                              self_trace=True)
        collector.accept([span(42)])
        collector.flush()
        assert "zipkin-tpu" in store.get_all_service_names()
        assert "collector ingest" in store.get_span_names("zipkin-tpu")
        # Exactly one self span for one processed batch (no feedback).
        self_spans = [
            s for s in store.spans
            if "zipkin-tpu" in s.service_names
        ]
        assert len(self_spans) == 1


class TestSelfTraceRoundTrip:
    def test_api_request_trace_queryable_by_id(self):
        """Acceptance: the self-trace span for an API round trip is
        fetchable through /api/trace/{id} using the echoed trace id."""
        reg = obs.Registry()
        store = InMemorySpanStore()
        collector = Collector(store, concurrency=1, registry=reg)
        api = ApiServer(QueryService(store), collector, registry=reg)
        resp_headers = []
        api.handle("GET", "/api/services", {},
                   response_headers=resp_headers)
        tid_hex = dict(resp_headers)["X-B3-TraceId"]
        collector.flush()
        status, body = api.handle("GET", f"/api/trace/{tid_hex}", {})
        assert status == 200
        assert body[0]["annotations"][0]["endpoint"]["serviceName"] == \
            "zipkin-tpu"


class TestDeviceCounterBlock:
    def _store(self):
        from zipkin_tpu.store.device import StoreConfig
        from zipkin_tpu.store.tpu import TpuSpanStore

        return TpuSpanStore(StoreConfig(
            capacity=1 << 10, ann_capacity=1 << 12,
            bann_capacity=1 << 11, max_services=32, max_span_names=128,
            max_annotation_values=256, max_binary_keys=64,
            cms_width=1 << 10, hll_p=8, quantile_buckets=256,
        ), registry=obs.Registry())

    def test_block_fields_and_memo(self):
        from zipkin_tpu.store import device as dev

        store = self._store()
        store.apply([span(1), span(2)])
        blk = store.counter_block()
        assert set(blk) == set(dev.COUNTER_BLOCK_FIELDS)
        assert blk["spans_seen"] == 2
        assert blk["ring_occupancy"] == 2 and blk["ring_laps"] == 0
        assert blk["batches"] == 1
        # Memoized between ingest steps: same dict object back.
        assert store.counter_block() is blk
        store.apply([span(3)])
        blk2 = store.counter_block()
        assert blk2 is not blk and blk2["spans_seen"] == 3
        # counters() keeps every legacy key + the host guards.
        c = store.counters()
        for key in ("spans_seen", "anns_seen", "banns_seen", "batches",
                    "key_claim_drops", "sweeps", "index_hits",
                    "index_scan_fallbacks", "anns_truncated",
                    "banns_truncated", "ring_occupancy"):
            assert key in c, key

    def test_step_census_memoized(self):
        store = self._store()
        census = store.step_census(n_spans=64, n_anns=128, n_banns=64)
        assert census["scatter"] > 0 and census["sort"] > 0
        assert store.step_census(n_spans=64, n_anns=128,
                                 n_banns=64) is census

    def test_counter_block_lowering_has_no_scatters(self):
        """The telemetry fetch is a pure read: no scatter/sort ops may
        ever lower from it (the zero-extra-passes design claim)."""
        import re

        from zipkin_tpu.store import device as dev

        store = self._store()
        text = dev.counter_block.lower(store.state).as_text()
        for op in ("scatter", "sort"):
            assert not re.findall(rf'"stablehlo\.{op}"', text), op

    def test_ingest_latency_sketch_fills(self):
        reg = obs.Registry()
        from zipkin_tpu.store.device import StoreConfig
        from zipkin_tpu.store.tpu import TpuSpanStore

        store = TpuSpanStore(StoreConfig(
            capacity=1 << 10, ann_capacity=1 << 12,
            bann_capacity=1 << 11, max_services=32, max_span_names=128,
            max_annotation_values=256, max_binary_keys=64,
            cms_width=1 << 10, hll_p=8, quantile_buckets=256,
        ), registry=reg)
        store.RUN_AHEAD = 0  # every launch waits for itself
        store.apply([span(9)])
        d = reg.as_dict()
        assert d["zipkin_store_ingest_launches_total"] == 1
        assert d["zipkin_store_ingest_step_seconds_count"] == 1


class TestSuspectStore:
    def test_slab_timeout_marks_store_suspect(self, tmp_path,
                                              monkeypatch):
        """ADVICE r5 regression: a slab-save timeout (slow fake device)
        must flag the store so donating ingest and the next save refuse
        to race the orphaned reader; joining the orphan clears it."""
        import jax

        from zipkin_tpu import checkpoint
        from zipkin_tpu.store.base import StoreSuspectError

        store = TestDeviceCounterBlock()._store()
        store.apply([span(1)])
        real_get = jax.device_get
        release = threading.Event()

        def slow_get(x):
            # Only the checkpoint's abandonable fetch threads are
            # daemons here; the main thread's gets pass through.
            if threading.current_thread().daemon and not release.is_set():
                release.wait(30)
            return real_get(x)

        with monkeypatch.context() as m:
            m.setattr(jax, "device_get", slow_get)
            with pytest.raises(TimeoutError):
                checkpoint.save(store, str(tmp_path / "ckpt"),
                                chunk_deadline_s=0.3, slab_retries=0)
        assert store.suspect
        # Donating writes refuse while the orphan may still read state.
        with pytest.raises(StoreSuspectError):
            store.apply([span(2)])
        # The next save refuses too (it would cut a new snapshot over
        # buffers the orphan still reads).
        with pytest.raises(StoreSuspectError):
            checkpoint.save(store, str(tmp_path / "ckpt2"))
        # Un-wedge the fake device; joining the orphan clears the flag.
        release.set()
        store.ensure_writable(wait_s=10.0)
        assert not store.suspect
        store.apply([span(2)])
        assert store.counter_block()["spans_seen"] == 2
        checkpoint.save(store, str(tmp_path / "ckpt3"))
        restored = checkpoint.load(str(tmp_path / "ckpt3"))
        assert restored.counter_block()["spans_seen"] == 2


class TestQueryEngineMetricSplit:
    """The PR 4 ingest observation split applied to reads
    (query/engine.py): zipkin_query_serve_seconds{tier=...} is
    end-to-end including sketch/cache hits, zipkin_query_dispatch_
    seconds isolates actual device launch + D2H — sketch and cache
    answers must never appear in the dispatch sketch."""

    def _engine_app(self):
        from zipkin_tpu.store.device import StoreConfig
        from zipkin_tpu.store.tpu import TpuSpanStore
        from zipkin_tpu.tracegen import generate_traces

        reg = obs.Registry()
        store = TpuSpanStore(StoreConfig(
            capacity=1 << 10, ann_capacity=1 << 12,
            bann_capacity=1 << 11, max_services=32, max_span_names=64,
            max_annotation_values=256, max_binary_keys=64,
            cms_width=1 << 10, hll_p=8, quantile_buckets=256,
        ), registry=reg)
        spans = [s for t in generate_traces(n_traces=12, max_depth=3,
                                            n_services=4) for s in t]
        store.apply(spans)
        service = QueryService(store, coalesce_window_s=0.0,
                               registry=reg)
        api = ApiServer(service, collector=None, registry=reg)
        return store, service, api, reg

    def test_serve_dispatch_split_exposed(self):
        store, service, api, reg = self._engine_app()
        end_ts = 1 << 61
        svc0 = sorted(store.get_all_service_names())[0]
        service.get_service_names()           # sketch tier
        service.get_span_names(svc0)          # sketch tier
        q = [("name", svc0, None, end_ts, 5)]
        service.engine.get_trace_ids_multi(q)  # index tier (dispatch)
        service.engine.get_trace_ids_multi(q)  # cache tier
        status, payload = api.handle("GET", "/metrics", {})
        assert status == 200
        text = payload.body.decode()
        assert "# TYPE zipkin_query_serve_seconds summary" in text
        assert "# TYPE zipkin_query_dispatch_seconds summary" in text
        for tier in ("sketch", "index", "cache"):
            assert (f'zipkin_query_serve_seconds{{tier="{tier}"'
                    in text), (tier, text)
            assert (f'zipkin_query_serve_seconds_count'
                    f'{{tier="{tier}"}}' in text), tier
        assert "zipkin_query_cache_hits_total 1" in text
        assert "zipkin_query_cache_entries 1" in text
        # Coalesce amortization sketches (batch size satellite).
        assert ("# TYPE zipkin_query_coalesce_batch_size summary"
                in text)
        assert "zipkin_query_coalesce_batch_queries_count 1" in text

    def test_sketch_and_cache_hits_never_count_as_dispatch(self):
        store, service, api, reg = self._engine_app()
        eng = service.engine
        svc0 = sorted(store.get_all_service_names())[0]
        q = [("name", svc0, None, 1 << 61, 5)]
        eng.get_trace_ids_multi(q)  # one real dispatch
        d0 = eng.h_dispatch.count
        assert d0 >= 1
        for _ in range(5):
            service.get_service_names()                # sketch
            eng.service_duration_quantiles(svc0, [0.5])  # sketch
            eng.get_trace_ids_multi(q)                 # cache hit
        assert eng.h_dispatch.count == d0  # no new device launches
        serve_sketch = eng.h_serve.labels(tier="sketch").count
        serve_cache = eng.h_serve.labels(tier="cache").count
        assert serve_sketch >= 10 and serve_cache >= 5
        # End-to-end sketch serves stay microsecond-scale (the whole
        # point): p99 well under the device dispatch floor.
        p99 = eng.h_serve.labels(tier="sketch").quantile_values([0.99])
        assert p99[0] < 0.01, p99


# ---------------------------------------------------------------------------
# One span for every stage of a Log call (obs.stage), on the profiler's
# clock; the gauges that joined /metrics with them
# ---------------------------------------------------------------------------

CALL_SPANS = {
    "ingest.call", "ingest.read_frame", "ingest.decode",
    "store.lock_wait", "store.encode", "wal.append", "wal.durable_wait",
    "store.commit", "store.dispatch", "store.device_sync_wait",
    "wal.fsync", "lineage.flush",
}
PIPELINE_SPANS = {"pipeline.feed_stall", "pipeline.h2d", "pipeline.commit"}


def _host_events(profile_dir):
    """{span name: [stats dict per event]} of the stage spans on the
    host planes of a capture, through the by-hand reducer's own reader
    (scripts/trace_stages.py), which this puts under test too."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "trace_stages", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "scripts", "trace_stages.py"))
    trace_stages = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(trace_stages)
    spans, modules = trace_stages.read(profile_dir)
    assert modules == []  # no device plane on the CPU
    assert trace_stages.reduce(spans, modules)["stages"]
    events = {}
    for name, _line, _start, _dur, stats in spans:
        events.setdefault(name, []).append(stats)
    return events


class TestStageSpans:
    def test_stage_observes_family_child_or_given_sketch(self):
        fam = obs.stage_family()
        assert obs.default_registry().get(
            "zipkin_ingest_stage_seconds") is fam
        child = fam.labels(stage="unit_test")
        n0 = child.count
        with obs.stage("obs.unit_test", unit=None) as st:
            pass
        assert child.count == n0 + 1 and st.seconds >= 0.0
        own = obs.LatencySketch("own_seconds", "h")
        with obs.stage("obs.unit_test", own, unit=7) as st:
            st.less = 10.0  # a wait timed elsewhere: never negative
        assert own.count == 1 and own.sum == 0.0
        assert child.count == n0 + 1
        # done() ends the span early, once
        with obs.stage("obs.unit_test", own) as st:
            st.done()
            first = st.seconds
        assert own.count == 2 and st.seconds == first
        text = obs.default_registry().render_text()
        assert 'zipkin_ingest_stage_seconds_count{stage="unit_test"}' in text

    @pytest.mark.parametrize("depth", [0, 2], ids=["serial", "pipelined"])
    def test_capture_holds_every_span_by_name(self, tmp_path, monkeypatch,
                                              depth):
        """A CPU capture through capture() while the served write path
        ingests three thrift batches: every span of the table is on a
        host line of the .xplane.pb, the units of wal.append,
        pipeline.h2d and store.commit agree, and the capture's own
        start and stop take under a second (the Python tracer, off
        now, took seconds)."""
        import time

        from zipkin_tpu.ingest.receiver import ResultCode
        from zipkin_tpu.obs import profile as obs_profile
        from zipkin_tpu.testing.scribe_rig import ScribeRig
        from zipkin_tpu.tracegen import generate_traces

        rig = ScribeRig(str(tmp_path / "wal"), pipeline_depth=depth,
                        lineage=True)
        spans = [s for t in generate_traces(n_traces=24, max_depth=3,
                                            n_services=6) for s in t]
        try:
            # compile outside the capture; then every launch waits
            assert rig.log(spans[0::4]) == ResultCode.OK
            rig.store.drain_pipeline()
            rig.tracker.flush()
            rig.store.drain_pipeline()
            rig.store.RUN_AHEAD = 0
            if depth:
                # a commit slow enough that the queues fill: the feed
                # stall is a span only where the queue was full
                real = rig.store._commit_unit

                def slow_commit(unit):
                    time.sleep(0.2)  # an ack takes a 50 ms group commit
                    real(unit)

                rig.store._commit_unit = slow_commit
            obs_profile.capture(0.01)  # the profiler's one-off start-up
            drove = []

            def drive(_seconds):
                t0 = time.thread_time()
                for i in (1, 2, 3):
                    for _ in range(3 if depth else 1):
                        assert rig.log(spans[i::4]) == ResultCode.OK
                rig.store.drain_pipeline()
                rig.tracker.flush()
                rig.store.drain_pipeline()
                drove.append(time.thread_time() - t0)

            # capture() sleeps through its window: drive it instead
            # (its own `time` only; time.sleep is every thread's)
            import types

            monkeypatch.setattr(obs_profile, "time",
                                types.SimpleNamespace(sleep=drive))
            # this thread's CPU seconds, which the other workers of a
            # loaded test run do not stretch: the Python tracer's start
            # and stop were work on the capturing thread
            t0 = time.thread_time()
            out_dir, _ = obs_profile.capture(1.0, str(tmp_path / "prof"))
            start_and_stop = time.thread_time() - t0 - drove[0]
        finally:
            rig.store.__dict__.pop("_commit_unit", None)
            rig.close()
        events = _host_events(out_dir)
        want = CALL_SPANS | (PIPELINE_SPANS if depth else set())
        assert want <= set(events), sorted(want - set(events))
        units = {name: {e["unit"] for e in events[name] if "unit" in e}
                 for name in ("wal.append", "store.commit")
                 + (("pipeline.h2d",) if depth else ())}
        assert units["wal.append"] and len(set(map(frozenset,
                                                    units.values()))) == 1
        assert all("call" in e and "conn" in e
                   for e in events["ingest.call"])
        assert all(e["bytes"] > 0 for e in events["ingest.read_frame"])
        assert start_and_stop < 1.0, start_and_stop

    def test_ingest_step_names_its_phases(self):
        """jax.named_scope round the sections of ingest_step: the names
        reach the lowered text (metadata only), so a device trace's
        tf_op says which phase an op belongs to."""
        from zipkin_tpu.store import device as dev
        from zipkin_tpu.testing.scribe_rig import CONFIG
        from zipkin_tpu.tracegen import generate_traces

        spans = [s for t in generate_traces(n_traces=4, max_depth=3,
                                            n_services=4) for s in t]
        from zipkin_tpu.store.tpu import TpuSpanStore

        store = TpuSpanStore(CONFIG, registry=obs.Registry())
        batch = store.codec.encode(spans)
        db = dev.make_device_batch(
            batch, name_lc_id=store._name_lc_ids(batch),
            indexable=np.ones(batch.n_spans, bool),
            pad_spans=64, pad_anns=256, pad_banns=128)
        text = dev.ingest_step.lower(store.state, db).as_text(
            debug_info=True)
        for phase in ("ring_write", "annotation_ring_write",
                      "span_table_insert", "dependency_join",
                      "index_segments", "index_write", "sketch_update",
                      "counters"):
            assert f"ingest.{phase}" in text, phase
        store.close()

    def test_daemon_metrics_carry_the_tcp_door_and_the_frontiers(
            self, tmp_path):
        """/metrics of the all-in-one daemon with --scribe-port: the
        TCP receiver's own entry accounting (not only the HTTP
        route's), the WAL's two frontiers and, where the backend
        reports them, the device's memory."""
        import socket

        from zipkin_tpu.ingest.receiver import ResultCode
        from zipkin_tpu.ingest.scribe_server import ScribeClient
        from zipkin_tpu.main.example import (
            build_app,
            build_parser,
            start_scribe,
        )
        from zipkin_tpu.testing.scribe_rig import log_entries

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        args = build_parser().parse_args([
            "--capacity", "256", "--window-seconds", "0",
            "--no-self-trace-ingest", "--host", "127.0.0.1",
            "--wal-dir", str(tmp_path / "wal"), "--scribe-port", str(port)])
        store, collector, api, _shipper = build_app(args)
        srv = start_scribe(args, store, collector, api)
        # The first call compiles the fused step: on a loaded test host
        # that takes longer than the client's default 10 s.
        client = ScribeClient("127.0.0.1", port, timeout_s=120.0)
        try:
            assert client.log(log_entries([span(1), span(2)])) \
                == ResultCode.OK
            status, payload = api.handle("GET", "/metrics", {})
        finally:
            client.close()
            srv.shutdown()
            srv.server_close()
            collector.close()
            store.wal.close()
        assert status == 200
        text = payload.body.decode()
        samples = dict(line.rsplit(" ", 1) for line in text.splitlines()
                       if line and not line.startswith("#"))
        tcp = 'zipkin_scribe_entries{transport="tcp",result="%s"}'
        assert samples[tcp % "received"] == "2"
        assert samples[tcp % "pushed_back"] == "0"
        assert samples[
            'zipkin_scribe_entries{transport="http",result="received"}'
        ] == "0"
        assert float(samples["zipkin_wal_last_seq"]) >= 1
        assert (float(samples["zipkin_wal_durable_seq"])
                == float(samples["zipkin_wal_last_seq"]))  # it was acked
        assert "# TYPE zipkin_device_memory_bytes gauge" in text
        import jax

        if jax.devices()[0].memory_stats():
            assert 'zipkin_device_memory_bytes{kind="peak"}' in text
