"""WAL-shipped replication (zipkin_tpu.replicate + store/replica):
device-free replica bitwise agreement at a fixed frontier, the
durable-only ship bound (un-acked tail absent in full), gap/idempotent
apply semantics, the TCP ship path incl. anchor bootstrap, warm-standby
follow + promote, replica retention, the pre-rev-14 cold-resync compat
path, and (slow lane) crash-during-ship reconnect/recovery/truncation
races."""

import os
import json
import tempfile
import time

import numpy as np
import pytest

from zipkin_tpu.replicate import (
    Follower,
    ReplicaTarget,
    ShipClient,
    ShipServer,
    StandbyTarget,
    WalShipper,
)
from zipkin_tpu.store.device import config_from_dict
from zipkin_tpu.store import device as dev
from zipkin_tpu.store.archive import TieredSpanStore
from zipkin_tpu.store.replica import ReplicaSpanStore, ReplicaReadOnlyError
from zipkin_tpu.store.tpu import TpuSpanStore
from zipkin_tpu.testing.crash import states_bitwise_equal
from zipkin_tpu.tracegen import generate_traces
from zipkin_tpu.wal import WalReplayError, WriteAheadLog, recover

CFG = dev.StoreConfig(
    capacity=1 << 10, ann_capacity=1 << 12, bann_capacity=1 << 11,
    max_services=32, max_span_names=256, max_annotation_values=256,
    max_binary_keys=64, cms_width=1 << 10, hll_p=8,
    quantile_buckets=512,
)


def _spans(n=2400, n_traces=500, seed_services=12):
    traces = generate_traces(n_traces=n_traces, max_depth=3,
                             n_services=seed_services)
    return [s for t in traces for s in t][:n]


def _feed(store, spans, chunk=128):
    for i in range(0, len(spans), chunk):
        store.apply(spans[i:i + chunk])


def _mirror_equal(a, b) -> bool:
    return all(np.array_equal(x, y) for x, y in zip(a, b))


@pytest.fixture()
def wal_dir(tmp_path):
    return str(tmp_path / "wal")


def _replay_into_replica(wal, replica, from_seq=0):
    for seq, payload in wal.replay(from_seq):
        replica.apply_record(seq, payload)


class TestReplicaAgreement:
    def test_replica_bitwise_agreement_at_fixed_frontier(self, wal_dir):
        """The acceptance gate: a device-free replica fed only WAL
        records answers the sketch tier AND row/index reads identical
        to the tiered primary at the same applied frontier — mirror
        arrays bitwise equal to the primary's device aggregates."""
        import jax

        primary = TieredSpanStore(TpuSpanStore(CFG))
        wal = WriteAheadLog(wal_dir, fsync="off")
        primary.attach_wal(wal)
        spans = _spans()
        _feed(primary, spans)
        replica = ReplicaSpanStore(CFG, background_compaction=False)
        try:
            _replay_into_replica(wal, replica)
            hot = primary.hot
            st = hot.state
            device_arrays = [np.asarray(a) for a in jax.device_get((
                st.svc_hist, st.ann_svc_counts, st.name_presence,
                st.ann_value_counts, st.bann_key_counts,
                st.hll_traces, st.win_epoch, st.win_counts,
                st.win_sums, st.win_mm))]
            assert _mirror_equal(device_arrays,
                                 replica.sketch_mirror.arrays())
            # Catalogs + aggregates.
            assert (replica.get_all_service_names()
                    == primary.get_all_service_names())
            svcs = sorted(primary.get_all_service_names())
            for svc in svcs[:4]:
                assert (replica.get_span_names(svc)
                        == primary.get_span_names(svc)), svc
                assert (replica.service_duration_quantiles(
                    svc, [0.5, 0.95, 0.99])
                    == primary.service_duration_quantiles(
                        svc, [0.5, 0.95, 0.99])), svc
                assert (replica.top_annotations(svc)
                        == primary.top_annotations(svc)), svc
                assert (replica.top_binary_keys(svc)
                        == primary.top_binary_keys(svc)), svc
            assert (replica.estimated_unique_traces()
                    == primary.estimated_unique_traces())
            # Row + index reads (cold segments vs hot+cold federation).
            tids = sorted({s.trace_id for s in spans[::37]})[:20]
            assert (replica.get_spans_by_trace_ids(tids)
                    == primary.get_spans_by_trace_ids(tids))
            assert (replica.traces_exist(tids)
                    == primary.traces_exist(tids))
            assert (replica.get_traces_duration(tids)
                    == primary.get_traces_duration(tids))
            end_ts = 1 << 62
            for svc in svcs[:4]:
                assert (replica.get_trace_ids_by_name(
                    svc, None, end_ts, 10)
                    == primary.get_trace_ids_by_name(
                        svc, None, end_ts, 10)), svc
            # Staleness is explicit.
            assert replica.applied_seq() == wal.last_seq
            f0 = replica.write_frontier()
            assert replica.write_frontier() == f0
        finally:
            replica.close()
            wal.close()

    def test_unacked_tail_absent_in_full(self, wal_dir):
        """The ship feed is bounded by the DURABLE frontier: records
        the primary has not fsynced are never handed to a follower, so
        a primary crash can never leave a replica ahead of recovery."""
        primary = TpuSpanStore(CFG)
        # Huge group-commit interval: appends stay un-durable until an
        # explicit sync — the durable frontier visibly lags.
        wal = WriteAheadLog(wal_dir, fsync="interval", interval_s=3600)
        primary.attach_wal(wal)
        shipper = WalShipper(primary, wal)
        _feed(primary, _spans(n=600, n_traces=120))
        assert wal.durable_seq < wal.last_seq
        got = shipper.fetch("f1", 0, 1 << 30)
        assert got is not None
        records, last, durable = got
        assert last == wal.last_seq and durable == wal.durable_seq
        assert all(seq <= durable for seq, _ in records)
        assert len(records) == durable
        wal.sync()
        records2, _, durable2 = shipper.fetch("f1", durable, 1 << 30)
        assert durable2 == wal.last_seq
        assert [s for s, _ in records2] == list(
            range(durable + 1, wal.last_seq + 1))
        shipper.close()
        wal.close()

    def test_replica_gap_rejected_duplicate_skipped(self, wal_dir):
        primary = TpuSpanStore(CFG)
        wal = WriteAheadLog(wal_dir, fsync="off")
        primary.attach_wal(wal)
        _feed(primary, _spans(n=600, n_traces=120))
        records = list(wal.replay(0))
        assert len(records) >= 3
        replica = ReplicaSpanStore(CFG, background_compaction=False)
        try:
            replica.apply_record(*records[0])
            # Duplicate: idempotent no-op.
            assert replica.apply_record(*records[0]) == 0
            # Gap: lineage error, nothing applied.
            with pytest.raises(WalReplayError):
                replica.apply_record(*records[2])
            assert replica.applied_seq() == records[0][0]
            # In-order continues fine.
            replica.apply_record(*records[1])
            assert replica.applied_seq() == records[1][0]
            # Writes are refused.
            with pytest.raises(ReplicaReadOnlyError):
                replica.apply([])
            with pytest.raises(ReplicaReadOnlyError):
                replica.set_time_to_live(1, 60.0)
        finally:
            replica.close()
            wal.close()

    def test_replica_retention_drops_old_segments(self, wal_dir):
        primary = TpuSpanStore(CFG)
        wal = WriteAheadLog(wal_dir, fsync="off")
        primary.attach_wal(wal)
        spans = _spans(n=2000, n_traces=400)
        _feed(primary, spans)
        replica = ReplicaSpanStore(CFG, retain_spans=512,
                                   background_compaction=False)
        try:
            _replay_into_replica(wal, replica)
            segs = replica.archive.snapshot()
            assert segs, "retention dropped everything"
            lo = min(s.gid_lo for s in segs)
            wp = replica.counters()["replica_wp"]
            assert lo >= wp - 512 - CFG.capacity  # whole segments only
            # Recent traces still read; the sketch tier still covers
            # the WHOLE history (mirror is lifetime state).
            recent = [spans[-1].trace_id]
            assert replica.get_spans_by_trace_ids(recent)
            assert (replica.estimated_unique_traces()
                    == primary.estimated_unique_traces())
        finally:
            replica.close()
            wal.close()


class TestShipWire:
    def _serve(self, primary):
        shipper = WalShipper(primary)
        server = ShipServer(shipper, host="127.0.0.1", port=0)
        server.serve_in_thread()
        return shipper, server, server.server_address[1]

    def test_tcp_follow_and_anchor_bootstrap(self, wal_dir):
        primary = TieredSpanStore(TpuSpanStore(CFG))
        wal = WriteAheadLog(wal_dir, fsync="off")
        primary.attach_wal(wal)
        shipper, server, port = self._serve(primary)
        spans = _spans(n=1600, n_traces=320)
        half = 768
        _feed(primary, spans[:half])
        client = ShipClient("127.0.0.1", port, "t1", mode="replica")
        hello = client.connect()
        assert config_from_dict(hello["config"]) == CFG
        replica = ReplicaSpanStore(CFG, background_compaction=False)
        follower = Follower(ReplicaTarget(replica), client,
                            poll_interval_s=0.002).start()
        _feed(primary, spans[half:])
        wal.sync()
        try:
            assert follower.drain(60.0), follower.status()
            assert _mirror_equal(
                primary.hot.ensure_sketch_mirror().arrays(),
                replica.sketch_mirror.arrays())
            status = follower.status()
            assert status["lagRecords"] == 0
            assert status["role"] == "replica"
            assert shipper.status()["followers"]["t1"]["cursor"] >= 1
            # Anchor bootstrap: release the pin, truncate the whole
            # log, and bring up a SECOND replica from nothing — it
            # must adopt the anchor (sketch tier exact from genesis)
            # and resume at the primary's frontier.
            wal.drop_cursor("t1")
            assert wal.truncate(wal.last_seq) >= 1
            c2 = ShipClient("127.0.0.1", port, "t2", mode="replica")
            c2.connect()
            rep2 = ReplicaSpanStore(CFG, background_compaction=False)
            f2 = Follower(ReplicaTarget(rep2), c2,
                          poll_interval_s=0.002)
            try:
                assert f2.step() is True  # NEED_ANCHOR -> adopt
                assert rep2.applied_seq() == wal.last_seq
                assert _mirror_equal(
                    replica.sketch_mirror.arrays(),
                    rep2.sketch_mirror.arrays())
                assert (rep2.estimated_unique_traces()
                        == primary.estimated_unique_traces())
                # Row coverage starts at the anchor (documented):
                # no segments yet, sketch tier fully live.
                assert len(rep2.archive) == 0
            finally:
                f2.close()
                rep2.close()
        finally:
            follower.close()
            replica.close()
            server.shutdown()
            wal.close()

    def test_standby_follow_promote_bitwise(self, wal_dir):
        primary = TpuSpanStore(CFG)
        wal = WriteAheadLog(wal_dir, fsync="off")
        primary.attach_wal(wal)
        _shipper, server, port = self._serve(primary)
        spans = _spans(n=1600, n_traces=320)
        client = ShipClient("127.0.0.1", port, "sby", mode="standby")
        client.connect()
        standby = TpuSpanStore(CFG)
        follower = Follower(StandbyTarget(standby), client,
                            poll_interval_s=0.002).start()
        try:
            _feed(primary, spans)
            wal.sync()
            assert follower.drain(60.0), follower.status()
            promoted = follower.promote()
            assert promoted is standby
            assert states_bitwise_equal(primary.state, promoted.state)
            # The promoted store owns writes now.
            promoted.apply(spans[:32])
        finally:
            server.shutdown()
            wal.close()


class TestStandbyAck:
    def test_standby_acks_checkpoint_frontier_not_applied(
            self, wal_dir):
        """The retention pin must track what the standby can recover
        to on its OWN (its checkpointed frontier), never its volatile
        applied frontier — otherwise the primary may truncate records
        a crashed standby still needs, and a standby cannot
        anchor-bootstrap out of that hole."""
        primary = TpuSpanStore(CFG)
        wal = WriteAheadLog(wal_dir, fsync="off")
        primary.attach_wal(wal)
        _feed(primary, _spans(n=600, n_traces=120))
        shipper = WalShipper(primary, wal)
        standby = TpuSpanStore(CFG)
        target = StandbyTarget(standby)
        # Hand-drive one fetch round the way Follower.step does.
        got = shipper.fetch("sby", target.applied_seq(), 1 << 30,
                            ack=target.ack_seq())
        for seq, payload in got[0]:
            target.apply(seq, payload)
        assert target.applied_seq() == wal.last_seq
        # Applied is ahead, but NOTHING is locally durable yet: the
        # pin (ack) must still be 0 and truncation must delete nothing.
        assert target.ack_seq() == 0
        shipper.fetch("sby", target.applied_seq(), 1 << 30,
                      ack=target.ack_seq())
        assert wal.truncate(wal.last_seq) == 0
        assert [s for s, _ in wal.replay(0)][0] == 1
        # A successful local checkpoint advances the ack; only then
        # may the covered prefix go.
        target.note_checkpointed(target.applied_seq())
        assert target.ack_seq() == wal.last_seq
        shipper.fetch("sby", target.applied_seq(), 1 << 30,
                      ack=target.ack_seq())
        assert wal.truncate(wal.last_seq) >= 1
        shipper.close()
        wal.close()


class TestColdResync:
    def test_pre_rev14_checkpoint_plus_replicated_tail_resync(
            self, tmp_path):
        """The satellite: a standby restored from a PRE-rev-14
        checkpoint (no window leaves — empty arena) fed the replicated
        WAL tail must lazily resync its sketch mirror (the adopt_state
        path: restore marks it cold, ensure_sketch_mirror refetches)
        BITWISE to its own device aggregates, window twins included —
        and its lifetime sketches must match the uncrashed oracle."""
        import jax

        from zipkin_tpu import checkpoint

        cfg = CFG._replace(window_seconds=60, window_buckets=8)
        primary = TpuSpanStore(cfg)
        wal = WriteAheadLog(str(tmp_path / "wal"), fsync="off")
        primary.attach_wal(wal)
        spans = _spans(n=1200, n_traces=240)
        _feed(primary, spans[:600])
        path = str(tmp_path / "ckpt")
        checkpoint.save(primary, path)
        _feed(primary, spans[600:])  # the replicated tail
        wal.sync()

        # Doctor the snapshot into pre-rev-14 shape (the r13 compat
        # idiom: drop win_* leaves + window config keys).
        state_file = os.path.join(path, "state.npz")
        data = dict(np.load(state_file))
        for k in list(data):
            if k.startswith("win_"):
                del data[k]
        np.savez(state_file, **data)
        meta_file = os.path.join(path, "meta.json")
        with open(meta_file) as f:
            meta = json.load(f)
        meta["revision"] = 13
        for k in ("window_seconds", "window_buckets"):
            meta["config"].pop(k, None)
        meta["slab_crc32"] = {
            k: v for k, v in (meta.get("slab_crc32") or {}).items()
            if not k.startswith("win_")
        }
        with open(meta_file, "w") as f:
            json.dump(meta, f)

        standby = checkpoint.load(path, config_defaults={
            "window_seconds": 60, "window_buckets": 8,
        })
        assert standby.config.window_enabled
        assert not standby.sketch_mirror.warm  # restore marked cold
        target = StandbyTarget(standby)
        for seq, payload in wal.replay(int(standby._wal_applied)):
            target.apply(seq, payload)
        assert int(standby._wal_applied) == wal.last_seq
        # Lazy resync == the device truth, window twins included.
        m = standby.ensure_sketch_mirror()
        st = standby.state
        device_arrays = [np.asarray(a) for a in jax.device_get((
            st.svc_hist, st.ann_svc_counts, st.name_presence,
            st.ann_value_counts, st.bann_key_counts, st.hll_traces,
            st.win_epoch, st.win_counts, st.win_sums, st.win_mm))]
        assert _mirror_equal(device_arrays, m.arrays())
        # Lifetime sketches survive the rev-13 snapshot: they match
        # the uncrashed oracle exactly. (The window arena holds only
        # the post-checkpoint tail BY DESIGN — pre-14 snapshots carry
        # no arena; its twins are gated against the device above.)
        oracle_m = primary.ensure_sketch_mirror().arrays()
        assert _mirror_equal(oracle_m[:6], m.arrays()[:6])
        wal.close()


@pytest.mark.slow
class TestCrashDuringShip:
    def test_follower_reconnects_across_server_restart(self, wal_dir):
        """Crash-during-ship: the ship endpoint dies mid-stream; the
        follower backs off, reconnects when the endpoint returns
        (same port), and converges bitwise with nothing skipped."""
        primary = TpuSpanStore(CFG)
        wal = WriteAheadLog(wal_dir, fsync="off")
        primary.attach_wal(wal)
        shipper = WalShipper(primary)
        server = ShipServer(shipper, host="127.0.0.1", port=0)
        port = server.server_address[1]
        server.serve_in_thread()
        spans = _spans(n=2000, n_traces=400)
        client = ShipClient("127.0.0.1", port, "rc", mode="replica")
        client.connect()
        replica = ReplicaSpanStore(CFG, background_compaction=False)
        follower = Follower(ReplicaTarget(replica), client,
                            poll_interval_s=0.002).start()
        try:
            _feed(primary, spans[:768])
            deadline = time.monotonic() + 30
            while (replica.applied_seq() == 0
                    and time.monotonic() < deadline):
                time.sleep(0.005)
            assert replica.applied_seq() > 0
            # Kill the endpoint mid-stream, keep feeding.
            server.shutdown()
            server.server_close()
            _feed(primary, spans[768:1408])
            # Resurrect on the SAME port; follower reconnects itself.
            server = ShipServer(shipper, host="127.0.0.1", port=port)
            server.serve_in_thread()
            _feed(primary, spans[1408:])
            wal.sync()
            assert follower.drain(60.0), follower.status()
            assert _mirror_equal(
                primary.ensure_sketch_mirror().arrays(),
                replica.sketch_mirror.arrays())
        finally:
            follower.close()
            replica.close()
            server.shutdown()
            wal.close()

    def test_primary_crash_recovery_resumes_ship(self, wal_dir):
        """The primary process dies and recovers from its own WAL; the
        follower's cursor stays valid (prefix semantics) and the
        replica converges with the RECOVERED primary bitwise."""
        primary = TpuSpanStore(CFG)
        wal = WriteAheadLog(wal_dir, fsync="off")
        primary.attach_wal(wal)
        spans = _spans(n=1600, n_traces=320)
        _feed(primary, spans[:768])
        replica = ReplicaSpanStore(CFG, background_compaction=False)
        _replay_into_replica(wal, replica)
        cursor = replica.applied_seq()
        # "Crash": drop the store + log objects on the floor; recover
        # from disk exactly like the daemon boot path.
        wal.close()
        del primary
        wal2 = WriteAheadLog(wal_dir, fsync="off")
        recovered, stats = recover(
            None, wal2, fresh_store=lambda: TpuSpanStore(CFG))
        assert stats["replayed_records"] >= 1
        _feed(recovered, spans[768:])
        wal2.sync()
        _replay_into_replica(wal2, replica, from_seq=cursor)
        try:
            assert _mirror_equal(
                recovered.ensure_sketch_mirror().arrays(),
                replica.sketch_mirror.arrays())
            assert replica.applied_seq() == wal2.last_seq
        finally:
            replica.close()
            wal2.close()

    def test_truncation_never_outruns_pinned_follower(self, wal_dir):
        """Aggressive checkpoint-style truncation after every batch
        races the follower's fetches: the cursor pin means no record
        is ever skipped and the replica still converges bitwise."""
        primary = TpuSpanStore(CFG)
        wal = WriteAheadLog(wal_dir, fsync="off")
        primary.attach_wal(wal)
        shipper = WalShipper(primary)
        server = ShipServer(shipper, host="127.0.0.1", port=0)
        port = server.server_address[1]
        server.serve_in_thread()
        client = ShipClient("127.0.0.1", port, "pin", mode="replica")
        client.connect()
        replica = ReplicaSpanStore(CFG, background_compaction=False)
        follower = Follower(ReplicaTarget(replica), client,
                            poll_interval_s=0.001).start()
        spans = _spans(n=2000, n_traces=400)
        try:
            for i in range(0, len(spans), 128):
                primary.apply(spans[i:i + 128])
                # The checkpoint contract: everything applied is
                # covered — without the pin this deletes fetchable
                # history out from under the follower.
                wal.truncate(int(primary._wal_applied))
            wal.sync()
            assert follower.drain(60.0), follower.status()
            assert replica.applied_seq() == wal.last_seq
            assert _mirror_equal(
                primary.ensure_sketch_mirror().arrays(),
                replica.sketch_mirror.arrays())
        finally:
            follower.close()
            replica.close()
            server.shutdown()
            wal.close()
