"""Fleet observability (obs/fleet): causal batch-lineage tracing
across the ship/apply boundary, metrics federation, the stall
watchdog + flight recorder, B3 child-join on the API surface, and the
live primary+follower trace-propagation acceptance gate."""

import os
import threading
import time

import pytest

from zipkin_tpu import obs
from zipkin_tpu.obs import fleet as fobs
from zipkin_tpu.obs.fleet import (
    FleetObs,
    FlightRecorder,
    FollowerLineage,
    LineageTracker,
    Watchdog,
    make_span,
    merge_sketches,
    registry_snapshot,
    render_federated,
    span_from_wire,
    span_to_wire,
)


def _drain_spans():
    """A sink that collects flushed span batches."""
    got = []

    def sink(spans):
        got.extend(spans)

    return got, sink


class TestWireCodec:
    def test_roundtrip(self):
        w = span_to_wire(7, 9, 3, "wal append", "zipkin-tpu",
                         1_000_000, 42, {"seq": "5"})
        s = span_from_wire(w)
        assert s.trace_id == 7 and s.id == 9 and s.parent_id == 3
        assert s.name == "wal append"
        assert s.annotations[0].host.service_name == "zipkin-tpu"
        assert s.annotations[1].timestamp - s.annotations[0].timestamp == 42
        assert dict((b.key, b.value) for b in s.binary_annotations) == {
            "seq": "5"}

    def test_root_parent_none(self):
        s = span_from_wire(span_to_wire(1, 2, None, "r", "svc", 10, 1))
        assert s.parent_id is None


class TestLineageTracker:
    def test_stamp_sampling_cadence(self):
        got, sink = _drain_spans()
        t = LineageTracker(sink, sample_every=4)
        extras = [t.stamp() for _ in range(8)]
        assert all("ts" in e for e in extras)
        sampled = [i for i, e in enumerate(extras) if "b3" in e]
        assert sampled == [0, 4]  # first unit always traced

    def test_unit_spans_causally_linked(self):
        got, sink = _drain_spans()
        t = LineageTracker(sink, sample_every=1)
        extra = t.stamp()
        t.note_append(3, extra)
        t.on_durable(3)
        t.note_shipped(3, "r1")
        t.flush()
        by_name = {s.name: s for s in got}
        assert set(by_name) == {"ingest unit", "wal append", "wal fsync",
                                "ship"}
        root = by_name["ingest unit"]
        tid, sid = extra["b3"]
        assert root.trace_id == tid and root.id == sid
        assert root.parent_id is None
        for name in ("wal append", "wal fsync", "ship"):
            child = by_name[name]
            assert child.trace_id == tid
            assert child.parent_id == sid
            assert child.id != sid

    def test_remote_spans_join_same_trace(self):
        got, sink = _drain_spans()
        t = LineageTracker(sink, sample_every=1)
        extra = t.stamp()
        t.note_append(1, extra)
        tid, sid = extra["b3"]
        t.ingest_remote_spans("r1", [
            span_to_wire(tid, 12345, sid, "replica apply",
                         "zipkin-tpu-r1", 50, 7),
            {"broken": True},  # malformed entries drop, not raise
        ])
        t.flush()
        applied = [s for s in got if s.name == "replica apply"]
        assert len(applied) == 1
        assert applied[0].trace_id == tid and applied[0].parent_id == sid

    def test_suppressed_blocks_reentrant_flush(self):
        flushed = []

        def sink(spans):
            flushed.append(list(spans))

        t = LineageTracker(sink, sample_every=1)
        for seq in range(t.FLUSH_AT + 1):
            t.note_append(seq, t.stamp())
        with t.suppressed():
            t.flush()
            assert not flushed  # suppressed: nothing may emit
        t.flush()
        assert flushed and not t._buf

    def test_sink_failure_counts_drops_not_raises(self):
        reg = obs.Registry()

        def bad_sink(spans):
            raise RuntimeError("store down")

        t = LineageTracker(bad_sink, registry=reg, sample_every=1)
        t.note_append(1, t.stamp())
        t.flush()  # must not raise
        assert reg.get("zipkin_lineage_spans_dropped_total").value > 0

    def test_stage_sketch_observes(self):
        reg = obs.Registry()
        got, sink = _drain_spans()
        t = LineageTracker(sink, registry=reg, sample_every=1)
        t.note_append(1, t.stamp())
        t.on_durable(1)
        sk = reg.get("zipkin_lineage_stage_seconds")
        stages = {labels[0][1]
                  for _suffix, labels, _v in sk.samples()
                  if labels and labels[0][0] == "stage"}
        assert {"append", "fsync"} <= stages

    def test_slow_sink_holds_no_ack_and_loses_no_span(self, tmp_path):
        """The sink (store.apply in the daemon: the encode lock, a
        launch, at worst a compile of a minute) runs on the tracker's
        own thread, never on the WAL's group-commit thread: a sink
        that sleeps 2 s delays no wait_durable, and what buffers
        meanwhile (within MAX_PENDING) is all delivered."""
        from zipkin_tpu.wal import WriteAheadLog

        got = []
        sinking = threading.Event()

        def slow_sink(spans):
            sinking.set()
            time.sleep(2.0)
            got.extend(spans)

        reg = obs.Registry()
        t = LineageTracker(slow_sink, registry=reg, sample_every=1)
        wal = WriteAheadLog(str(tmp_path / "wal"), fsync="interval",
                            interval_s=0.01, registry=reg)
        wal.set_on_durable(t.on_durable)
        waits = []
        try:
            for i in range(40):  # 3 spans a unit: the sink wakes at 32
                seq = wal.append(b"unit %d" % i)
                t.note_append(seq, t.stamp())
                t0 = time.perf_counter()
                assert wal.wait_durable(seq, timeout=5.0)
                waits.append(time.perf_counter() - t0)
                if i == 20:
                    assert sinking.wait(5.0)  # the slow call is running
            assert max(waits) < 0.5, max(waits)
        finally:
            wal.close()
        t.flush()  # waits for the call in flight, then sinks the rest
        assert len(got) == 3 * 40
        assert reg.get("zipkin_lineage_spans_dropped_total").value == 0

    def test_buffer_bounded_while_the_sink_is_stuck(self):
        """What no sink call has taken stays under MAX_PENDING spans;
        the rest is dropped and counted."""
        reg = obs.Registry()
        t = LineageTracker(lambda spans: None, registry=reg,
                           sample_every=1)
        for seq in range(t.MAX_PENDING):  # 2 spans a unit, none woken
            t.note_append(seq, t.stamp())
        assert len(t._buf) == t.MAX_PENDING
        assert reg.get("zipkin_lineage_spans_dropped_total").value \
            == t.MAX_PENDING

    def test_pending_bounded(self):
        got, sink = _drain_spans()
        t = LineageTracker(sink, sample_every=1)
        for seq in range(t.MAX_PENDING + 64):
            t.note_append(seq, t.stamp())
        assert len(t._pending) <= t.MAX_PENDING


class TestFollowerLineage:
    def _record(self, tracker):
        """One stamped WAL-style payload via the real encoder (an
        empty launch group still carries the full json header)."""
        from zipkin_tpu.wal.record import encode_unit

        extra = tracker.stamp()
        return encode_unit([], [], {}, extra=extra), extra

    def test_lag_and_apply_span_backhaul(self):
        got, sink = _drain_spans()
        t = LineageTracker(sink, sample_every=1)
        payload, extra = self._record(t)
        f = FollowerLineage("r1", mode="replica")
        f.observe_record(9, payload, apply_s=0.002)
        lag = f.lag_seconds()
        assert lag is not None and 0 <= lag < 60
        spans = f.take_spans()
        assert len(spans) == 1
        w = spans[0]
        tid, sid = extra["b3"]
        assert w["traceId"] == tid and w["parentId"] == sid
        assert w["name"] == "replica apply"
        assert w["service"] == "zipkin-tpu-r1"
        assert f.take_spans() == []  # drained

    def test_unstamped_record_harmless(self):
        from zipkin_tpu.wal.record import encode_unit

        f = FollowerLineage("r1")
        f.observe_record(1, encode_unit([], [], {}), apply_s=0.001)
        assert f.lag_seconds() is None
        assert f.take_spans() == []

    def test_backlog_bounded(self):
        got, sink = _drain_spans()
        t = LineageTracker(sink, sample_every=1)
        f = FollowerLineage("r1")
        for seq in range(f.MAX_BACKLOG + 32):
            payload, _ = self._record(t)
            f.observe_record(seq, payload, apply_s=0.001)
        assert len(f.take_spans()) <= f.MAX_BACKLOG

    def test_metrics_snapshot_throttled(self):
        reg = obs.Registry()
        reg.register(obs.Counter("x_total", "h")).inc()
        now = [1000.0]
        f = FollowerLineage("r1", registry=reg, clock=lambda: now[0])
        snap = f.maybe_metrics_snapshot()
        assert snap is not None and snap["v"] == 1
        assert f.maybe_metrics_snapshot() is None  # within interval
        now[0] += f.METRICS_PUSH_INTERVAL_S + 0.1
        assert f.maybe_metrics_snapshot() is not None

    def test_lag_gauge_registered(self):
        reg = obs.Registry()
        f = FollowerLineage("r1", registry=reg)
        assert reg.get("zipkin_replication_lag_seconds").value == 0.0
        got, sink = _drain_spans()
        t = LineageTracker(sink, sample_every=1)
        payload, _ = self._record(t)
        f.observe_record(1, payload, apply_s=0.001)
        assert reg.get("zipkin_replication_lag_seconds").value >= 0.0


class TestFederation:
    def _registry(self, counter=3.0, sketch_vals=(0.01, 0.02)):
        reg = obs.Registry()
        reg.register(obs.Counter("f_req_total", "requests")).inc(counter)
        sk = reg.register(obs.LatencySketch("f_lat_seconds", "latency"))
        for v in sketch_vals:
            sk.observe(v)
        return reg

    def test_single_source_bitwise_vs_own_scrape(self):
        """A federated render of one process's snapshot differs from
        its own scrape ONLY by the injected labels — every value
        formats identically (same _fmt path)."""
        reg = self._registry()
        own = reg.render_text()
        fed = render_federated(
            [((("role", "primary"),), registry_snapshot(reg))])
        own_vals = sorted(line.rsplit(" ", 1)[1]
                          for line in own.splitlines()
                          if line and not line.startswith("#"))
        fed_vals = sorted(line.rsplit(" ", 1)[1]
                          for line in fed.splitlines()
                          if line and not line.startswith("#"))
        assert own_vals == fed_vals

    def test_merged_scrape_no_double_counting(self):
        a = self._registry(counter=3.0)
        b = self._registry(counter=5.0)
        fed = render_federated([
            ((("role", "primary"),), registry_snapshot(a)),
            ((("role", "follower"), ("follower", "r1")),
             registry_snapshot(b)),
        ])
        rows = [l for l in fed.splitlines()
                if l.startswith("f_req_total")]
        assert len(rows) == 2
        assert any('role="primary"' in r and r.endswith(" 3")
                   for r in rows)
        assert any('follower="r1"' in r and r.endswith(" 5")
                   for r in rows)

    def test_sketch_monoid_merge(self):
        import numpy as np

        a = obs.LatencySketch("m_seconds", "h")
        b = obs.LatencySketch("m_seconds", "h")
        both = obs.LatencySketch("m_seconds", "h")
        for v in (0.001, 0.01, 0.1):
            a.observe(v)
            both.observe(v)
        for v in (0.2, 0.4):
            b.observe(v)
            both.observe(v)
        merged = merge_sketches("m_seconds", "h", [
            fobs._sketch_state(a), fobs._sketch_state(b)])
        assert np.array_equal(merged.counts, both.counts)
        assert merged.moments.n == both.moments.n
        assert list(merged.samples()) == list(both.samples())

    def test_fleet_status_rolls_up(self):
        reg_a = obs.Registry()
        sk = reg_a.register(obs.LatencySketch(
            "zipkin_replication_visible_lag_seconds", "lag"))
        sk.observe(0.01)
        reg_b = obs.Registry()
        sk2 = reg_b.register(obs.LatencySketch(
            "zipkin_replication_visible_lag_seconds", "lag"))
        sk2.observe(0.03)

        fleet = FleetObs(
            role="primary", registry=reg_a,
            remote_sources=lambda: [
                ((("role", "follower"), ("follower", "r1")),
                 registry_snapshot(reg_b))])
        st = fleet.status()
        assert len(st["processes"]) == 2
        merged = st["merged"]["zipkin_replication_visible_lag_seconds"]
        assert merged["count"] == 2


class TestFlightRecorder:
    def test_bounded_ring_keeps_newest(self):
        r = FlightRecorder(capacity=4)
        for i in range(10):
            r.record("k", severity="info", i=i)
        evs = r.events()
        assert len(evs) == 4
        assert [e["fields"]["i"] for e in evs] == [6, 7, 8, 9]
        assert [e["fields"]["i"] for e in r.events(limit=2)] == [8, 9]

    def test_event_shape(self):
        r = FlightRecorder()
        r.record("watchdog", severity="error", probe="fsync",
                 reason="parked")
        (e,) = r.events()
        assert e["kind"] == "watchdog" and e["severity"] == "error"
        assert e["fields"]["probe"] == "fsync"
        assert "tsUs" in e and "seq" in e


class TestWatchdog:
    def test_transitions_recorded_once(self):
        rec = FlightRecorder()
        reg = obs.Registry()
        wd = Watchdog(recorder=rec, registry=reg)
        state = {"ok": True}
        wd.add_probe("p", lambda: (state["ok"],
                                   None if state["ok"] else "stuck",
                                   1.0))
        assert wd.check()["ready"] is True
        state["ok"] = False
        h = wd.check()
        assert h["ready"] is False and h["live"] is True
        assert h["reasons"][0]["probe"] == "p"
        wd.check()  # still failing: no new transition event
        state["ok"] = True
        wd.check()
        kinds = [(e["kind"], e["fields"].get("probe"))
                 for e in rec.events()]
        assert kinds.count(("watchdog_trip", "p")) == 1
        assert kinds.count(("watchdog_clear", "p")) == 1
        assert reg.get("zipkin_watchdog_trips_total").value == 1
        assert reg.get("zipkin_watchdog_failing_probes").value == 0

    def test_probe_exception_is_a_failure(self):
        wd = Watchdog()

        def boom():
            raise RuntimeError("probe died")

        wd.add_probe("boom", boom)
        h = wd.check()
        assert h["ready"] is False
        assert "probe died" in h["reasons"][0]["reason"]

    def test_fsync_parked_probe(self, tmp_path):
        from zipkin_tpu.wal import WriteAheadLog

        wal = WriteAheadLog(str(tmp_path / "w"), fsync="off")
        try:
            probe = fobs.fsync_parked_probe(wal)
            assert probe()[0] is True
            wal._sync_error = RuntimeError("disk gone")
            ok, reason, _ = probe()
            assert ok is False and "disk gone" in reason
        finally:
            wal._sync_error = None
            wal.close()

    def test_follower_lag_probe_thresholds(self):
        st = {"lagRecords": 5, "lagSeconds": 1.0}
        probe = fobs.follower_lag_probe(lambda: st,
                                        max_lag_records=10,
                                        max_lag_seconds=30.0)
        assert probe()[0] is True
        st["lagRecords"] = 50
        assert probe()[0] is False
        st["lagRecords"] = 5
        st["lagSeconds"] = 31.0
        assert probe()[0] is False


class TestDispatcherSpanSink:
    def test_fused_batch_parents_under_request_context(self):
        from types import SimpleNamespace

        from zipkin_tpu.parallel.dispatch import CrossShardDispatcher

        store = SimpleNamespace(
            CAT_BUNDLE_KEYS=frozenset(),
            _cat_direct=lambda key: {"n": 1})
        reg = obs.Registry()
        d = CrossShardDispatcher(store, registry=reg)
        spans = []
        d.span_sink = SimpleNamespace(
            record_span=lambda *a, **k: spans.append((a, k)))
        token = fobs.set_request_context(0xAB, 0xCD)
        try:
            assert d.cat("svc") == {"n": 1}
        finally:
            fobs.reset_request_context(token)
        d.close()
        assert spans, "dispatch span not recorded"
        (args, _kw) = spans[0]
        trace_id, parent_id, name = args[0], args[1], args[2]
        assert (trace_id, parent_id) == (0xAB, 0xCD)
        assert name == "shard dispatch"

    def test_no_context_no_span(self):
        from types import SimpleNamespace

        from zipkin_tpu.parallel.dispatch import CrossShardDispatcher

        store = SimpleNamespace(CAT_BUNDLE_KEYS=frozenset(),
                                _cat_direct=lambda key: {})
        d = CrossShardDispatcher(store, registry=obs.Registry())
        spans = []
        d.span_sink = SimpleNamespace(
            record_span=lambda *a, **k: spans.append(a))
        d.cat("svc")
        d.close()
        assert not spans

    def test_queue_age_idle_zero(self):
        from types import SimpleNamespace

        from zipkin_tpu.parallel.dispatch import CrossShardDispatcher

        d = CrossShardDispatcher(
            SimpleNamespace(CAT_BUNDLE_KEYS=frozenset(),
                            _cat_direct=lambda key: {}),
            registry=obs.Registry())
        assert d.queue_age_s() == 0.0
        d.close()


class TestApiFleetSurface:
    def _api(self, fleet):
        from zipkin_tpu.api import ApiServer
        from zipkin_tpu.ingest.collector import Collector
        from zipkin_tpu.query.service import QueryService
        from zipkin_tpu.store.memory import InMemorySpanStore

        store = InMemorySpanStore()
        collector = Collector(store, concurrency=0, self_trace=False)
        api = ApiServer(QueryService(store), collector, fleet=fleet)
        return store, collector, api

    def test_health_flips_on_failing_probe(self):
        rec = FlightRecorder()
        wd = Watchdog(recorder=rec)
        state = {"ok": True}
        wd.add_probe("fsync", lambda: (
            state["ok"], None if state["ok"] else "wal fsync parked",
            None))
        fleet = FleetObs(role="primary", registry=obs.Registry(),
                         watchdog=wd, recorder=rec)
        _store, _collector, api = self._api(fleet)
        code, body = api.handle("GET", "/api/health", {}, headers={})
        assert code == 200 and body["ready"] is True
        state["ok"] = False
        code, body = api.handle("GET", "/api/health", {}, headers={})
        assert code == 503 and body["ready"] is False
        assert body["reasons"][0]["reason"] == "wal fsync parked"
        # The trip is visible in the flight recorder.
        code, body = api.handle("GET", "/debug/events", {}, headers={})
        assert code == 200
        assert any(e["kind"] == "watchdog_trip" for e in body["events"])

    def test_health_without_fleet_always_ready(self):
        _store, _collector, api = self._api(None)
        code, body = api.handle("GET", "/api/health", {}, headers={})
        assert code == 200 and body["ready"] is True

    def test_fleet_endpoint_and_merged_scrape(self):
        reg = obs.Registry()
        reg.register(obs.Counter("p_total", "h")).inc(2)
        freg = obs.Registry()
        freg.register(obs.Counter("p_total", "h")).inc(7)
        fleet = FleetObs(
            role="primary", registry=reg,
            remote_sources=lambda: [
                ((("role", "follower"), ("follower", "r1")),
                 registry_snapshot(freg))])
        _store, _collector, api = self._api(fleet)
        code, body = api.handle("GET", "/api/fleet", {}, headers={})
        assert code == 200 and body["role"] == "primary"
        assert len(body["processes"]) == 2
        code, raw = api.handle("GET", "/metrics", {"fleet": "1"},
                               headers={})
        text = raw.body.decode("utf-8")
        assert code == 200
        rows = [l for l in text.splitlines() if l.startswith("p_total")]
        assert any('role="primary"' in r and r.endswith(" 2")
                   for r in rows)
        assert any('follower="r1"' in r and r.endswith(" 7")
                   for r in rows)

    def test_plain_scrape_unchanged_by_fleet_param_absence(self):
        fleet = FleetObs(role="primary", registry=obs.Registry())
        _store, _collector, api = self._api(fleet)
        code, raw = api.handle("GET", "/metrics", {}, headers={})
        assert code == 200
        text = raw.body.decode("utf-8")
        # Plain scrape stays the per-process registry: no injected
        # federation labels anywhere.
        assert 'role="primary"' not in text


@pytest.mark.slow
class TestLiveFleetTrace:
    """The acceptance gate: a primary+follower pair under ingest
    produces ONE causally-linked trace spanning
    encode → WAL append → fsync → ship → follower apply, queryable
    from the primary's own store."""

    def test_ship_pair_single_trace(self, tmp_path):
        from zipkin_tpu.replicate import (
            Follower,
            ReplicaTarget,
            ShipClient,
            ShipServer,
            WalShipper,
        )
        from zipkin_tpu.store import device as dev
        from zipkin_tpu.store.replica import ReplicaSpanStore
        from zipkin_tpu.store.tpu import TpuSpanStore
        from zipkin_tpu.tracegen import generate_traces
        from zipkin_tpu.wal import WriteAheadLog

        cfg = dev.StoreConfig(
            capacity=1 << 9, ann_capacity=1 << 11,
            bann_capacity=1 << 10, max_services=32,
            max_span_names=256, max_annotation_values=256,
            max_binary_keys=64, cms_width=1 << 10, hll_p=8,
            quantile_buckets=512)
        reg = obs.Registry()
        primary = TpuSpanStore(cfg)
        wal = WriteAheadLog(str(tmp_path / "wal"), fsync="off")
        primary.attach_wal(wal)
        tracker = LineageTracker(primary.apply, registry=reg,
                                 sample_every=1)
        primary.attach_lineage(tracker)
        shipper = WalShipper(primary, registry=reg, tracker=tracker)
        server = ShipServer(shipper, host="127.0.0.1", port=0)
        server.serve_in_thread()
        port = server.server_address[1]

        freg = obs.Registry()
        replica = ReplicaSpanStore(cfg, background_compaction=False)
        flin = FollowerLineage("r1", mode="replica", registry=freg)
        client = ShipClient("127.0.0.1", port, follower="r1",
                            mode="replica")
        follower = Follower(ReplicaTarget(replica), client,
                            registry=freg, lineage=flin)
        try:
            spans = [s for t in generate_traces(
                n_traces=20, max_depth=3, n_services=4) for s in t][:100]
            primary.apply(spans)
            wal.sync()
            deadline = time.monotonic() + 30.0
            while (replica.applied_seq() < wal.last_seq
                   and time.monotonic() < deadline):
                follower.step()
            assert replica.applied_seq() >= wal.last_seq
            follower.step()  # backhauls the buffered apply spans
            tracker.flush()
            wal.sync()

            found = primary.get_trace_ids_by_name(
                "zipkin-tpu", None, 1 << 62, 50)
            assert found, "no lineage trace recorded"
            want = {"ingest unit", "wal append", "wal fsync", "ship",
                    "replica apply"}
            complete = None
            for itid in found:
                trace = primary.get_spans_by_trace_ids(
                    [itid.trace_id])[0]
                names = {s.name for s in trace}
                if want <= names:
                    complete = trace
                    break
            assert complete is not None, (
                "no trace spans the full pipeline")
            root = next(s for s in complete
                        if s.name == "ingest unit"
                        and s.parent_id is None)
            for s in complete:
                if s.name in want - {"ingest unit"}:
                    assert s.parent_id == root.id, s.name
                    assert s.trace_id == root.trace_id
            applied = next(s for s in complete
                           if s.name == "replica apply")
            assert (applied.annotations[0].host.service_name
                    == "zipkin-tpu-r1")
            # Satellite 2: visible-lag gauge is live on the follower.
            assert flin.lag_seconds() is not None
            assert (freg.get("zipkin_replication_lag_seconds").value
                    >= 0.0)
            # Federation: both processes in one merged scrape.
            fleet = FleetObs(role="primary", registry=reg,
                             tracker=tracker,
                             remote_sources=shipper.fleet_sources,
                             replication=shipper.status)
            text = fleet.federated_text()
            assert 'role="primary"' in text
            assert 'follower="r1"' in text
            st = fleet.status()
            assert len(st["processes"]) == 2
        finally:
            server.shutdown()
            server.server_close()
            client.close()
            replica.close()
            wal.close()
