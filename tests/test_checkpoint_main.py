"""Checkpoint save/restore + composed mains smoke tests."""

import numpy as np
import pytest

from zipkin_tpu import checkpoint
from zipkin_tpu.models.span import Annotation, BinaryAnnotation, Endpoint, Span
from zipkin_tpu.store.device import StoreConfig
from zipkin_tpu.store.tpu import TpuSpanStore

CFG = StoreConfig(
    capacity=1 << 9, ann_capacity=1 << 11, bann_capacity=1 << 10,
    max_services=16, max_span_names=64, max_annotation_values=64,
    max_binary_keys=16, cms_width=1 << 9, hll_p=6, quantile_buckets=128,
)

WEB = Endpoint(1, 80, "web")
API = Endpoint(2, 80, "api")


def rpc(tid, sid, parent, t0, t1):
    return Span(tid, "op", sid, parent, (
        Annotation(t0, "cs", WEB),
        Annotation(t0 + 1, "sr", API),
        Annotation(t1 - 1, "ss", API),
        Annotation(t1, "cr", WEB),
    ), (BinaryAnnotation("k", b"v", host=API),))


class TestCheckpoint:
    def test_save_restore_roundtrip(self, tmp_path):
        store = TpuSpanStore(CFG)
        store.apply([rpc(1, 1, None, 100, 200), rpc(1, 2, 1, 110, 150)])
        store.set_time_to_live(1, 777.0)
        path = str(tmp_path / "ckpt")
        checkpoint.save(store, path)

        restored = checkpoint.load(path)
        # Queries behave identically on the restored store.
        assert restored.get_spans_by_trace_ids([1]) == \
            store.get_spans_by_trace_ids([1])
        assert restored.get_all_service_names() == {"web", "api"}
        assert restored.get_time_to_live(1) == 777.0
        assert restored.counters() == store.counters()
        got = {(l.parent, l.child) for l in restored.get_dependencies().links}
        assert got == {(l.parent, l.child) for l in store.get_dependencies().links}

    def test_restored_store_accepts_writes(self, tmp_path):
        store = TpuSpanStore(CFG)
        store.apply([rpc(1, 1, None, 100, 200)])
        path = str(tmp_path / "ckpt")
        checkpoint.save(store, path)
        restored = checkpoint.load(path)
        restored.apply([rpc(2, 1, None, 300, 400)])
        assert restored.traces_exist([1, 2]) == {1, 2}
        # Dictionary ids survived: the same service maps to the same id.
        assert restored.dicts.services.get("api") == store.dicts.services.get("api")

    def test_legacy_snapshot_migrates_live_links(self, tmp_path):
        """A pre-revision-4 snapshot carried unarchived links only
        implicitly: resident ring rows past the dep_archived_gid
        watermark, joined on demand by the retired ring join. load()
        must reconstruct exactly those links into the streaming-join
        window (no loss, no double count)."""
        import json
        import os

        store = TpuSpanStore(CFG)
        # Trace 3's child arrives WITHOUT its parent: under the legacy
        # schema it sat in the ring awaiting the on-demand join; the
        # migration must queue it in the pending ring so the parent
        # arriving post-upgrade still links.
        store.apply([rpc(1, 1, None, 100, 200), rpc(1, 2, 1, 110, 150),
                     rpc(2, 7, None, 300, 400), rpc(2, 8, 7, 310, 330),
                     rpc(3, 21, 20, 500, 550)])
        expected = [(l.parent, l.child, l.duration_moments.count)
                    for l in store.get_dependencies().links]
        assert expected  # the fixture must actually produce links

        path = str(tmp_path / "ckpt")
        checkpoint.save(store, path)

        # Rewrite the snapshot into the revision-3 layout: links exist
        # only in the ring + a zero watermark; the streaming-join leaves
        # don't exist yet.
        state_file = os.path.join(path, "state.npz")
        data = dict(np.load(state_file))
        for gone in ("span_tab", "pend_key", "pend_dur", "pend_tsf",
                     "pend_tsl", "pend_pos", "dep_window",
                     "dep_window_ts"):
            del data[gone]
        data["dep_moments"] = np.zeros_like(data["dep_moments"])
        data["dep_banks"] = np.zeros_like(data["dep_banks"])
        data["dep_archived_gid"] = np.int64(0)
        np.savez_compressed(state_file, **data)
        meta_file = os.path.join(path, "meta.json")
        with open(meta_file) as f:
            meta = json.load(f)
        meta["revision"] = 3
        cfg = dict(meta["config"])
        cfg.pop("span_tab_slots", None)
        cfg.pop("pend_slots", None)
        meta["config"] = cfg
        with open(meta_file, "w") as f:
            json.dump(meta, f)

        restored = checkpoint.load(path)
        got = [(l.parent, l.child, l.duration_moments.count)
               for l in restored.get_dependencies().links]
        assert got == expected
        # The orphan child queued by the migration links once its
        # parent arrives post-restore (dep_sweep resolves the pending
        # entry against the newly inserted parent).
        before = sum(l.duration_moments.count
                     for l in restored.get_dependencies().links)
        restored.apply([rpc(3, 20, None, 490, 560)])
        after = sum(l.duration_moments.count
                    for l in restored.get_dependencies().links)
        assert after >= before + 1  # the orphan child linked

    def test_chunked_save_resumes_after_wedged_transfer(self, tmp_path,
                                                        monkeypatch):
        """A transfer that wedges mid-save (r4: one 544MB device_get
        hung >70 min) must cost the failed leaves only: the staged
        leaves survive on disk, and a retry with an unchanged state
        generation skips them and completes a CONSISTENT snapshot."""
        store = TpuSpanStore(CFG)
        store.apply([rpc(1, 1, None, 100, 200), rpc(1, 2, 1, 110, 150)])
        path = str(tmp_path / "ckpt")

        real_get = checkpoint._bounded_get
        fail = {"after": 5}  # wedge every transfer past the 5th

        def flaky(x, deadline_s):
            if deadline_s is not None and fail["after"] <= 0:
                raise TimeoutError("simulated wedge")
            fail["after"] -= 1
            return real_get(x, None)

        monkeypatch.setattr(checkpoint, "_bounded_get", flaky)
        with pytest.raises(TimeoutError):
            checkpoint.save(store, path, chunk_deadline_s=5.0,
                            slab_retries=0)
        staging = path + ".staging"
        assert __import__("os").path.isdir(staging)
        assert not __import__("os").path.isdir(path)  # nothing partial

        # Retry with a healthy device: staged leaves are reused. The
        # simulated wedge carried no orphan thread, so the suspect
        # stamp needs the operator override (a real timeout's orphan
        # finishes and ensure_writable clears the flag itself).
        store.clear_suspect()
        monkeypatch.setattr(checkpoint, "_bounded_get", real_get)
        stats = checkpoint.save(store, path, chunk_deadline_s=5.0)
        assert stats["resumed_leaves"] > 0
        assert not __import__("os").path.isdir(staging)  # cleaned up
        restored = checkpoint.load(path)
        assert restored.get_spans_by_trace_ids([1]) == \
            store.get_spans_by_trace_ids([1])
        assert restored.counters() == store.counters()

    def test_stale_staging_discarded_after_writes(self, tmp_path,
                                                  monkeypatch):
        """Writes between save attempts change the state generation:
        the stale staged leaves must be DISCARDED, never mixed into the
        new cut (a mixed snapshot would be silently inconsistent)."""
        store = TpuSpanStore(CFG)
        store.apply([rpc(1, 1, None, 100, 200)])
        path = str(tmp_path / "ckpt")

        real_get = checkpoint._bounded_get
        fail = {"after": 5}

        def flaky(x, deadline_s):
            if deadline_s is not None and fail["after"] <= 0:
                raise TimeoutError("simulated wedge")
            fail["after"] -= 1
            return real_get(x, None)

        monkeypatch.setattr(checkpoint, "_bounded_get", flaky)
        with pytest.raises(TimeoutError):
            checkpoint.save(store, path, chunk_deadline_s=5.0,
                            slab_retries=0)
        store.clear_suspect()  # simulated wedge: no orphan to join
        monkeypatch.setattr(checkpoint, "_bounded_get", real_get)
        store.apply([rpc(2, 3, None, 300, 400)])  # generation changes
        stats = checkpoint.save(store, path, chunk_deadline_s=5.0)
        assert stats["resumed_leaves"] == 0  # stale stage discarded
        restored = checkpoint.load(path)
        assert restored.get_spans_by_trace_ids([2]) == \
            store.get_spans_by_trace_ids([2])

    def test_sweep_between_attempts_discards_staging(self, tmp_path,
                                                     monkeypatch):
        """dep_sweep mutates dep_window/pend_key while moving NO write
        cursor — the one mutation a cursor-only fingerprint would miss
        (review r5). The device-side sweeps counter must change the
        generation so stale staged leaves are discarded, not mixed."""
        store = TpuSpanStore(CFG)
        # A child whose parent arrives later leaves pending-ring state
        # for the sweep to fold.
        store.apply([rpc(1, 2, 7, 110, 150)])
        store.apply([rpc(1, 7, None, 100, 200)])
        path = str(tmp_path / "ckpt")
        real_get = checkpoint._bounded_get
        fail = {"after": 5}

        def flaky(x, deadline_s):
            if deadline_s is not None and fail["after"] <= 0:
                raise TimeoutError("simulated wedge")
            fail["after"] -= 1
            return real_get(x, None)

        monkeypatch.setattr(checkpoint, "_bounded_get", flaky)
        with pytest.raises(TimeoutError):
            checkpoint.save(store, path, chunk_deadline_s=5.0,
                            slab_retries=0)
        store.clear_suspect()  # simulated wedge: no orphan to join
        monkeypatch.setattr(checkpoint, "_bounded_get", real_get)
        before = int(store.counters()["sweeps"])
        store.get_dependencies()  # triggers the pending sweep
        assert int(store.counters()["sweeps"]) > before
        stats = checkpoint.save(store, path, chunk_deadline_s=5.0)
        assert stats["resumed_leaves"] == 0  # sweep changed generation
        restored = checkpoint.load(path)
        got = {(l.parent, l.child)
               for l in restored.get_dependencies().links}
        assert got == {(l.parent, l.child)
                       for l in store.get_dependencies().links}

    def test_wedged_slab_fails_fast_with_bounded_lock_hold(
            self, tmp_path, monkeypatch):
        """Regression: the FIRST slab timeout must fail
        the save immediately — no retry/backoff while the
        writer-blocking read lock is held (the retry enqueues behind
        the wedged transfer and can never succeed until it clears, so
        it only ever extended the ingest stall). A slow fake device
        wedges every transfer after the first few; the save must
        return within ~one deadline (no backoff sleeps, no second
        attempt), stamp the store suspect, and leave the staged leaves
        for the resume path."""
        import os
        import time

        store = TpuSpanStore(CFG)
        store.apply([rpc(1, 1, None, 100, 200)])
        path = str(tmp_path / "ckpt")

        deadline = 0.3
        real_get = checkpoint._bounded_get
        calls = {"n": 0, "wedged": 0}

        def slow_device(x, deadline_s):
            calls["n"] += 1
            if deadline_s is not None and calls["n"] > 3:
                # Slow fake device: block for the full deadline the
                # way a blocked transfer does, then surface the timeout.
                calls["wedged"] += 1
                time.sleep(deadline_s)
                err = TimeoutError("simulated slow device")
                raise err
            return real_get(x, None)

        monkeypatch.setattr(checkpoint, "_bounded_get", slow_device)
        t0 = time.perf_counter()
        with pytest.raises(TimeoutError):
            # slab_retries is deliberately > 0: fail-fast must ignore
            # it (the parameter is kept for call-site compatibility).
            checkpoint.save(store, path, chunk_deadline_s=deadline,
                            slab_retries=5)
        held = time.perf_counter() - t0
        # Exactly ONE wedged transfer was attempted — no retries — so
        # the lock hold is bounded by one deadline plus the healthy
        # leaves' transfer time, far below even a single retry cycle
        # (deadline + backoff + deadline).
        assert calls["wedged"] == 1
        assert held < 2 * deadline + 5.0
        # The store is stamped suspect (orphan bookkeeping) and the
        # staged leaves survived for the resume.
        assert store.suspect
        assert os.path.isdir(path + ".staging")
        # Resume with a healthy device completes and clears nothing
        # it shouldn't: the snapshot restores.
        monkeypatch.setattr(checkpoint, "_bounded_get", real_get)
        store.clear_suspect()
        stats = checkpoint.save(store, path, chunk_deadline_s=5.0)
        assert stats["resumed_leaves"] > 0
        restored = checkpoint.load(path)
        assert restored.get_spans_by_trace_ids([1]) == \
            store.get_spans_by_trace_ids([1])

    def test_chunked_save_slabs_large_leaves(self, tmp_path,
                                             monkeypatch):
        """Leaves larger than the slab budget transfer in pieces and
        reassemble bit-exactly."""
        monkeypatch.setattr(checkpoint, "_SLAB_BYTES", 1 << 12)
        store = TpuSpanStore(CFG)
        store.apply([rpc(1, 1, None, 100, 200), rpc(1, 2, 1, 110, 150)])
        path = str(tmp_path / "ckpt")
        stats = checkpoint.save(store, path, chunk_deadline_s=30.0)
        # 4KB slabs over >=several-hundred-KB state: many slabs.
        assert stats["slabs"] > 50
        assert stats["mb_per_s_avg"] > 0
        restored = checkpoint.load(path)
        assert restored.get_spans_by_trace_ids([1]) == \
            store.get_spans_by_trace_ids([1])
        assert restored.counters() == store.counters()

    def test_atomic_overwrite(self, tmp_path):
        store = TpuSpanStore(CFG)
        store.apply([rpc(1, 1, None, 100, 200)])
        path = str(tmp_path / "ckpt")
        checkpoint.save(store, path)
        store.apply([rpc(2, 1, None, 300, 400)])
        checkpoint.save(store, path)  # overwrite in place
        restored = checkpoint.load(path)
        assert restored.traces_exist([1, 2]) == {1, 2}


class TestMains:
    def test_tracegen_main_tpu_roundtrip(self):
        from zipkin_tpu.main.tracegen import run

        assert run(n_traces=3, max_depth=4, use_tpu=True, verbose=False)

    def test_tracegen_main_memory_roundtrip(self):
        from zipkin_tpu.main.tracegen import run

        assert run(n_traces=3, max_depth=4, use_tpu=False, verbose=False)

    def test_example_build_app_and_seed(self):
        from zipkin_tpu.main.example import build_app, build_parser, seed

        args = build_parser().parse_args(
            ["--memory-store", "--seed-traces", "2"]
        )
        store, collector, api, _shipper = build_app(args)
        seed(collector, 2)
        status, services = api.handle("GET", "/api/services", {})
        assert status == 200 and services
        # Runtime-adjustable sample rate (HttpVar parity).
        status, body = api.handle("POST", "/vars/sampleRate", {}, b"0.25")
        assert status == 200 and body["sampleRate"] == 0.25
        assert collector.sampler.rate == 0.25
        collector.close()


def test_pinned_traces_survive_checkpoint_restart(tmp_path):
    """Pin → save → load → flood: the eviction-exempt bank restores
    with the TTL, so the retention contract holds across restarts."""
    from zipkin_tpu.models.span import Annotation, Endpoint, Span
    from zipkin_tpu.store.device import StoreConfig
    from zipkin_tpu.store.tpu import TpuSpanStore
    from zipkin_tpu import checkpoint

    cfg = StoreConfig(
        capacity=256, ann_capacity=1024, bann_capacity=512,
        max_services=16, max_span_names=32, max_annotation_values=64,
        max_binary_keys=16, cms_width=256, hll_p=6, quantile_buckets=128,
    )
    store = TpuSpanStore(cfg)
    ep = Endpoint(1, 80, "pinned-svc")
    tid = 777
    store.apply([Span(tid, "op", 1, None,
                      (Annotation(10, "sr", ep), Annotation(20, "ss", ep)),
                      ())])
    store.set_time_to_live(tid, 30 * 24 * 3600.0)
    path = str(tmp_path / "ckpt")
    checkpoint.save(store, path)

    restored = checkpoint.load(path)
    assert restored.get_time_to_live(tid) == 30 * 24 * 3600.0
    noise_ep = Endpoint(2, 80, "noise")
    for i in range(0, 2 * cfg.capacity, 128):
        restored.apply([
            Span(10_000 + i + j, "n", 50_000 + i + j, None,
                 (Annotation(30 + j, "sr", noise_ep),), ())
            for j in range(128)
        ])
    got = restored.get_spans_by_trace_id(tid)
    assert len(got) == 1 and got[0].id == 1
    assert tid in restored.traces_exist([tid])


def test_pin_bank_dedups_redelivered_spans():
    from zipkin_tpu.models.span import Annotation, Endpoint, Span
    from zipkin_tpu.store.device import StoreConfig
    from zipkin_tpu.store.tpu import TpuSpanStore

    cfg = StoreConfig(
        capacity=256, ann_capacity=1024, bann_capacity=512,
        max_services=16, max_span_names=32, max_annotation_values=64,
        max_binary_keys=16, cms_width=256, hll_p=6, quantile_buckets=128,
    )
    store = TpuSpanStore(cfg)
    ep = Endpoint(1, 80, "svc")
    tid = 888
    span = Span(tid, "op", 1, None, (Annotation(10, "sr", ep),), ())
    store.apply([span])
    store.set_time_to_live(tid, 30 * 24 * 3600.0)
    # Transport retry re-delivers the identical span 5 times.
    for _ in range(5):
        store.apply([span])
    bank = store.pins.get(store.pins.tids().pop())
    assert len(bank) == 1


def test_sharded_checkpoint_roundtrip(tmp_path):
    """ShardedSpanStore snapshot -> restore over a fresh mesh: queries,
    sketches, and pinned banks all survive (the sharded analogue of the
    single-store durability contract)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from zipkin_tpu import checkpoint
    from zipkin_tpu.models.span import Annotation, Endpoint, Span
    from zipkin_tpu.parallel.shard import ShardedSpanStore
    from zipkin_tpu.store.device import StoreConfig
    from zipkin_tpu.tracegen import generate_traces

    n = min(4, len(jax.devices()))
    mesh = Mesh(np.array(jax.devices()[:n]), axis_names=("shard",))
    cfg = StoreConfig(
        capacity=256, ann_capacity=1024, bann_capacity=512,
        max_services=16, max_span_names=32, max_annotation_values=64,
        max_binary_keys=16, cms_width=256, hll_p=6, quantile_buckets=128,
    )
    store = ShardedSpanStore(mesh, cfg)
    spans = [s for t in generate_traces(n_traces=12, max_depth=3,
                                        n_services=6) for s in t]
    store.apply(spans)
    ep = Endpoint(1, 80, "pinsvc")
    store.apply([Span(4242, "p", 1, None, (Annotation(7, "sr", ep),), ())])
    store.set_time_to_live(4242, 30 * 24 * 3600.0)
    path = str(tmp_path / "sharded-ckpt")
    checkpoint.save(store, path)

    restored = checkpoint.load(path)
    assert restored.n == n
    assert restored.stored_span_count() == store.stored_span_count()
    svc = sorted(store.get_all_service_names())[0]
    want = store.get_trace_ids_by_name(svc, None, 2**62, 10)
    got = restored.get_trace_ids_by_name(svc, None, 2**62, 10)
    assert [(i.trace_id, i.timestamp) for i in want] == \
           [(i.trace_id, i.timestamp) for i in got]
    tid = want[0].trace_id
    assert [s.id for t in restored.get_spans_by_trace_ids([tid]) for s in t] \
        == [s.id for t in store.get_spans_by_trace_ids([tid]) for s in t]
    assert restored.get_time_to_live(4242) == 30 * 24 * 3600.0
    assert restored.get_spans_by_trace_id(4242)
    d1 = {(l.parent, l.child) for l in store.get_dependencies().links}
    d2 = {(l.parent, l.child) for l in restored.get_dependencies().links}
    assert d1 == d2


def test_sharded_legacy_snapshot_migrates(tmp_path):
    """Pre-revision-4 SHARDED snapshot: per-shard live-link migration,
    the [n_shards] write_pos fallback slicing, and the shard_map span-
    table rebuild must all restore links and cross-batch joins."""
    import json
    import os

    import jax
    import numpy as np
    from jax.sharding import Mesh

    from zipkin_tpu import checkpoint
    from zipkin_tpu.parallel.shard import ShardedSpanStore
    from zipkin_tpu.store.device import StoreConfig
    from zipkin_tpu.tracegen import generate_traces

    n = min(4, len(jax.devices()))
    mesh = Mesh(np.array(jax.devices()[:n]), axis_names=("shard",))
    cfg = StoreConfig(
        capacity=256, ann_capacity=1024, bann_capacity=512,
        max_services=16, max_span_names=32, max_annotation_values=64,
        max_binary_keys=16, cms_width=256, hll_p=6, quantile_buckets=128,
    )
    store = ShardedSpanStore(mesh, cfg)
    traces = generate_traces(n_traces=10, max_depth=3, n_services=6)
    parents = [t[0] for t in traces]
    children = [s for t in traces for s in t[1:]]
    store.apply(parents + children)
    expected = {(l.parent, l.child, l.duration_moments.count)
                for l in store.get_dependencies().links}
    assert expected

    path = str(tmp_path / "sharded-legacy")
    checkpoint.save(store, path)

    # Rewrite into the revision-3 layout: links only implicit in the
    # per-shard rings + zero watermarks; no streaming-join leaves.
    state_file = os.path.join(path, "state.npz")
    data = dict(np.load(state_file))
    for gone in ("span_tab", "pend_key", "pend_dur", "pend_tsf",
                 "pend_tsl", "pend_pos", "dep_window", "dep_window_ts"):
        del data[gone]
    data["dep_moments"] = np.zeros_like(data["dep_moments"])
    data["dep_banks"] = np.zeros_like(data["dep_banks"])
    data["dep_archived_gid"] = np.zeros(n, np.int64)
    np.savez_compressed(state_file, **data)
    meta_file = os.path.join(path, "meta.json")
    with open(meta_file) as f:
        meta = json.load(f)
    meta["revision"] = 3
    for k in ("span_tab_slots", "pend_slots"):
        meta["config"].pop(k, None)
    with open(meta_file, "w") as f:
        json.dump(meta, f)

    restored = checkpoint.load(path, mesh=mesh)
    got = {(l.parent, l.child, l.duration_moments.count)
           for l in restored.get_dependencies().links}
    assert got == expected
    # The rebuilt span table must resolve a child arriving post-restore
    # whose parent only exists in the checkpointed ring.
    late = [t[1] for t in generate_traces(n_traces=1, max_depth=2,
                                          n_services=6) if len(t) > 1]
    from zipkin_tpu.models.span import Annotation, Endpoint, Span

    parent = parents[0]
    ep = Endpoint(9, 80, sorted(restored.get_all_service_names())[0])
    child = Span(parent.trace_id, "late", 987654, parent.id,
                 (Annotation(50, "sr", ep), Annotation(60, "ss", ep)), ())
    restored.apply([child])
    after = {(l.parent, l.child) for l in restored.get_dependencies().links}
    assert len(after) >= len({(p, c) for p, c, _ in expected})
