"""Pipelined ingest (store/pipeline) — the r9 tentpole's guarantees.

The three-stage pipeline (encode ∥ H2D staging ∥ device commit) and
the async eviction sealer must change WHEN work happens, never WHAT
state results:

- a pipelined drive lands a device state bitwise identical to the
  serial path's (same chunk boundaries, same CHAIN_SIZES grouping,
  same pow2 pads — the determinism suite's replayability claim
  extended across the threading seam);
- async capture sealing produces the identical cold tier, and a slow
  sealer BOUNDS memory (the in-flight queue is the only buffer) by
  stalling ingest instead of growing;
- checkpoint saves taken mid-flight quiesce the pipeline and cut the
  archive manifest at the sealed frontier, so a restore never claims
  a window that was pulled but not yet sealed.
"""

from __future__ import annotations

import threading
import time

import jax
import numpy as np
import pytest

from zipkin_tpu.store import device as dev
from zipkin_tpu.store.archive import ArchiveParams, TieredSpanStore
from zipkin_tpu.store.tpu import TpuSpanStore
from zipkin_tpu.tracegen import generate_traces

# Same geometry as tests/test_determinism.py — shares its jit cache.
CONFIG = dev.StoreConfig(
    capacity=256, ann_capacity=1024, bann_capacity=512,
    max_services=16, max_span_names=32, max_annotation_values=64,
    max_binary_keys=16, cms_width=256, hll_p=6, quantile_buckets=128,
)


def _spans(n_traces=120, n_services=6):
    return [s for t in generate_traces(n_traces=n_traces, max_depth=3,
                                       n_services=n_services) for s in t]


def _leaves(state):
    flat, _ = jax.tree_util.tree_flatten(state)
    return [np.asarray(x) for x in flat]


def _assert_bitwise_equal(a_state, b_state):
    a, b = _leaves(a_state), _leaves(b_state)
    assert len(a) == len(b)
    for i, (x, y) in enumerate(zip(a, b)):
        assert x.dtype == y.dtype
        np.testing.assert_array_equal(
            x, y, err_msg=f"leaf {i} diverged pipelined vs serial"
        )


def _params():
    return ArchiveParams.for_config(
        CONFIG, compact_fanin=2, small_span_limit=CONFIG.capacity,
        bloom_bits=1 << 12, cms_width=1 << 9, hll_p=6,
    )


def test_pipelined_bitwise_matches_serial():
    spans = _spans()
    serial = TpuSpanStore(CONFIG)
    for i in range(0, len(spans), 40):
        serial.apply(spans[i:i + 40])
    piped = TpuSpanStore(CONFIG)
    with piped.pipelined(depth=3):
        for i in range(0, len(spans), 40):
            piped.apply(spans[i:i + 40])
        piped.drain_pipeline()
        # Reads during/after drain see everything accepted.
        assert (piped.counter_block()["spans_seen"]
                == serial.counter_block()["spans_seen"])
    _assert_bitwise_equal(serial.state, piped.state)
    serial.close()
    piped.close()


def test_pipelined_capture_matches_inline_sealing():
    """Pipelined ingest + ASYNC sealer == serial ingest + inline
    sealer: same device state, same capture windows, same segments —
    the sealer changes where the D2H+deflate runs, never what is
    captured (the pull still happens before any overwrite)."""
    spans = _spans(n_traces=260)[:4 * CONFIG.capacity]

    def drive(backlog, pipeline):
        hot = TpuSpanStore(CONFIG)
        hot.capture_backlog = backlog
        tiered = TieredSpanStore(hot, params=_params())
        if pipeline:
            hot.start_pipeline(3)
        for i in range(0, len(spans), 64):
            tiered.apply(spans[i:i + 64])
        hot.drain_pipeline()
        hot.seal_barrier()
        hot.stop_pipeline()
        return hot, tiered

    sh, st = drive(0, False)
    ph, pt = drive(2, True)
    _assert_bitwise_equal(sh.state, ph.state)
    cs, cp = st.counters(), pt.counters()
    assert cs["archive_cold_spans"] == cp["archive_cold_spans"] > 0
    assert (cs["archive_segments_written"]
            == cp["archive_segments_written"] >= 1)
    # Sealed frontier caught up with the pull clock after the barrier.
    assert ph._sealed_upto == ph._cap_upto > 0
    segs = pt.archive.snapshot()
    assert segs[0].gid_lo == 0
    for a, b in zip(segs, segs[1:]):
        assert a.gid_hi == b.gid_lo
    # Reads agree across the two sealing modes, gid dedup included.
    tids = sorted({s.trace_id for s in spans})
    sample = [tids[0], tids[len(tids) // 2], tids[-1]]
    assert (pt.get_spans_by_trace_ids(sample)
            == st.get_spans_by_trace_ids(sample))
    st.close()
    pt.close()


def test_capture_backpressure_bounds_memory():
    """A slow sealer must BOUND in-flight capture memory at the
    backlog (ingest stalls — the stall counter proves it fired) and
    still seal every window in order with no loss."""
    hot = TpuSpanStore(CONFIG)
    hot.capture_backlog = 1
    windows = []
    max_backlog = [0]

    def slow_sink(batch, gids, lo, hi, pull_s):
        max_backlog[0] = max(max_backlog[0], hot._sealer.queued())
        time.sleep(0.15)
        windows.append((lo, hi, batch.n_spans))

    hot.eviction_sink = slow_sink
    # Fat spans lap the annotation ring every ~33 spans, forcing a
    # capture window on nearly every chunk — far faster than the
    # sealer's 0.15s, so the 1-deep backlog must fill and stall.
    from zipkin_tpu.models.span import Annotation, Endpoint, Span

    ep = Endpoint(1, 80, "fat")
    spans = [
        Span(tid, "op", tid, None, tuple(
            [Annotation(1000 + 100 * tid, "sr", ep)]
            + [Annotation(1000 + 100 * tid + i, "custom", ep)
               for i in range(31)]
        ), ())
        for tid in range(1, 2 * CONFIG.capacity + 1)
    ]
    for i in range(0, len(spans), 64):
        hot.apply(spans[i:i + 64])
    hot.seal_barrier()
    assert len(windows) >= 4, "the drive must have captured repeatedly"
    # Bounded: the queue never grew past the backlog...
    assert max_backlog[0] <= 1
    # ...because ingest stalled on it (deliberate backpressure).
    assert float(hot._sealer.c_stall.value) > 0
    # No loss, no reorder: windows tile [0, cap_upto) contiguously.
    assert windows[0][0] == 0
    for (_, hi_a, _), (lo_b, _, _) in zip(windows, windows[1:]):
        assert hi_a == lo_b
    assert windows[-1][1] == hot._cap_upto == hot._sealed_upto
    hot.close()


def test_checkpoint_during_pipelined_ingest(tmp_path):
    """Threaded stress: concurrent queries + a mid-flight checkpoint
    save while the pipeline ingests with async capture enabled
    (SuspectGuard + RWLock interplay). The save must quiesce the
    pipeline + capture backlog, and the restored tiered store must
    have contiguous cold coverage — a pulled-but-unsealed window may
    never be claimed by the manifest."""
    from zipkin_tpu import checkpoint

    spans = _spans(n_traces=300)[:6 * CONFIG.capacity // 2]
    hot = TpuSpanStore(CONFIG)
    hot.capture_backlog = 2
    tiered = TieredSpanStore(hot, params=_params())
    hot.start_pipeline(3)
    errors = []
    stop_reads = threading.Event()

    def writer():
        try:
            for i in range(0, len(spans), 64):
                tiered.apply(spans[i:i + 64])
        except Exception as e:  # noqa: BLE001 — surfaced by the assert
            errors.append(e)

    def reader():
        end_ts = 1 << 60
        try:
            while not stop_reads.is_set():
                tiered.get_trace_ids_by_name("svc-0", None, end_ts, 5)
                tiered.traces_exist([spans[0].trace_id])
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    w = threading.Thread(target=writer)
    r = threading.Thread(target=reader)
    w.start()
    r.start()
    time.sleep(0.3)  # land the save mid-stream
    ckpt = tmp_path / "ckpt"
    checkpoint.save(tiered, str(ckpt))
    w.join()
    stop_reads.set()
    r.join()
    hot.drain_pipeline()
    hot.stop_pipeline()
    assert not errors, errors
    restored = checkpoint.load(str(ckpt))
    try:
        assert restored.get_all_service_names()
        # Cold coverage is contiguous from gid 0 to the restored
        # capture clock (capture_now at load flushed the tail).
        segs = restored.archive.snapshot()
        if segs:
            assert segs[0].gid_lo == 0
            for a, b in zip(segs, segs[1:]):
                assert a.gid_hi == b.gid_lo
            assert segs[-1].gid_hi == restored.hot._cap_upto
    finally:
        restored.close()
        tiered.close()


def test_zero_recompiles_in_pipelined_steady_state():
    """After a pipelined warm drive, a second pipelined drive over the
    same chunk shapes must hit only cached jit entries — the pow2
    staging buckets exist exactly so steady state never recompiles."""
    spans = _spans(n_traces=120)

    def drive():
        store = TpuSpanStore(CONFIG)
        with store.pipelined(depth=3):
            for i in range(0, len(spans), 40):
                store.apply(spans[i:i + 40])
            store.drain_pipeline()
        store.close()

    drive()  # warm (staged args key their own jit cache rows)
    before = dev.compile_count()
    drive()
    assert dev.compile_count() == before


def test_pipeline_lifecycle_and_error_surfacing():
    spans = _spans(n_traces=20)
    store = TpuSpanStore(CONFIG)
    pipe = store.start_pipeline(2)
    with pytest.raises(RuntimeError):
        store.start_pipeline(2)  # one pipeline per store
    store.apply(spans)
    store.drain_pipeline()
    store.stop_pipeline()
    # Feeding a stopped pipeline object raises; the store itself fell
    # back to the serial path and still works.
    with pytest.raises(RuntimeError):
        from zipkin_tpu.store.pipeline import IngestUnit

        pipe.feed(IngestUnit(None, 0, 0, 0, 1, False))
    store.apply(spans[:5])
    assert store.counter_block()["spans_seen"] == len(spans) + 5
    # A commit-side failure parks, re-raises ONCE on drain (the failed
    # units' spans are dropped, like a serial per-batch failure), and
    # the pipeline then keeps working — a transient fault must not
    # wedge the store permanently.
    store2 = TpuSpanStore(CONFIG)
    store2.start_pipeline(2)
    boom = RuntimeError("commit exploded")

    def bad_commit(unit):
        raise boom

    store2._commit_unit = bad_commit
    store2.apply(spans)
    with pytest.raises(RuntimeError, match="commit exploded"):
        store2.drain_pipeline()
    del store2._commit_unit  # fault clears; class method resumes
    store2.apply(spans[:5])
    store2.drain_pipeline()  # does not re-raise the surfaced error
    assert store2.counter_block()["spans_seen"] == 5
    store2.stop_pipeline()
    store.close()
    store2.close()


@pytest.mark.parametrize("depth", [0, 2], ids=["serial", "pipelined"])
def test_host_leads_the_device_by_run_ahead_launches(depth):
    """Every launch leaves a marker and waits for the one RUN_AHEAD
    launches back: never a drain of the whole queue, never more than
    RUN_AHEAD launches unwaited-for, and a marker outlives the
    donation of the state it was computed from."""
    from zipkin_tpu import obs

    reg = obs.Registry()
    store = TpuSpanStore(CONFIG, registry=reg)
    store.RUN_AHEAD = 2
    waits = obs.stage_family().labels(stage="device_sync_wait")
    before = waits.count
    spans = _spans(n_traces=28)
    n = 7
    if depth:
        store.start_pipeline(depth)
    for i in range(n):
        store.apply(spans[i::n])
        assert len(store._in_flight) <= store.RUN_AHEAD
    store.drain_pipeline()
    d = reg.as_dict()
    assert d["zipkin_store_ingest_launches_total"] == n
    assert waits.count - before == n - store.RUN_AHEAD
    assert d["zipkin_store_ingest_step_seconds_count"] == n - store.RUN_AHEAD
    # the two left are the newest launches', ready or not, and readable
    # though their states were donated since (the last one's apart)
    assert len(store._in_flight) == store.RUN_AHEAD
    assert [int(m) for m, _ in store._in_flight][-1] == int(
        store.state.write_pos)
    store.close()


def test_ingest_latency_metrics_split():
    """Dispatch time is always observed; a launch's dispatch until the
    device had run it is observed where the host waits for that launch
    (with RUN_AHEAD 0: at once, so even one write reports)."""
    from zipkin_tpu import obs

    reg = obs.Registry()
    store = TpuSpanStore(CONFIG, registry=reg)
    store.RUN_AHEAD = 0  # every launch waits for itself
    spans = _spans(n_traces=10)
    store.apply(spans)
    d = reg.as_dict()
    launches = d["zipkin_store_ingest_launches_total"]
    assert launches >= 1
    assert d["zipkin_store_ingest_dispatch_seconds_count"] == launches
    assert d["zipkin_store_ingest_step_seconds_count"] == launches
    # Dispatch until the device had run the launch: device compute
    # is in it.
    assert d["zipkin_store_ingest_step_seconds_sum"] > 0
    assert store.counters()["jit_compiles"] == dev.compile_count() > 0
    store.close()


@pytest.mark.parametrize("depth", [0, 2], ids=["serial", "pipelined"])
def test_stage_counts_per_log_call(tmp_path, depth):
    """N Log calls through a ScribeServer socket: every stage of the
    call is observed once a call (stage 1 twice on the serial path,
    round the journal), the sync wait once a wait, and the sketches
    the benchmark reads count what they counted before the stages
    were rewritten onto obs.stage (the parent commit gives the same
    numbers for this input: one launch unit, one append and, under
    fsync=batch, one fsync a call)."""
    from zipkin_tpu import obs
    from zipkin_tpu.ingest.receiver import ResultCode
    from zipkin_tpu.testing.scribe_rig import ScribeRig

    stages = ("call", "read_frame", "decode", "lock_wait", "encode",
              "durable_wait", "commit", "device_sync_wait")
    fam = obs.stage_family()
    rig = ScribeRig(str(tmp_path / "wal"), pipeline_depth=depth,
                    fsync="batch")
    try:
        before = {s: fam.labels(stage=s).count for s in stages}
        n = 5
        spans = _spans(n_traces=4 * n)
        for i in range(n):
            assert rig.log(spans[i::n]) == ResultCode.OK
        rig.store.drain_pipeline()
        # the last call's span ends on the handler's thread, after the
        # reply that the client has already read
        deadline = time.monotonic() + 5.0
        while (fam.labels(stage="call").count - before["call"] < n
               and time.monotonic() < deadline):
            time.sleep(0.01)
        count = {s: fam.labels(stage=s).count - before[s] for s in stages}
        d = rig.registry.as_dict()
    finally:
        rig.close()
    for s in ("call", "read_frame", "decode", "lock_wait",
              "durable_wait", "commit"):
        assert count[s] == n, (s, count)
    assert count["encode"] == (n if depth else 2 * n)
    syncs = d["zipkin_store_ingest_step_seconds_count"]
    # launch 5 waits for launch 1; the first RUN_AHEAD wait for none
    assert count["device_sync_wait"] == syncs == n - rig.store.RUN_AHEAD == 1
    assert d["zipkin_collector_write_seconds_count"] == n
    assert d["zipkin_store_ingest_dispatch_seconds_count"] == n
    assert d["zipkin_store_ingest_launches_total"] == n
    assert d["zipkin_wal_append_seconds_count"] == n
    assert d["zipkin_wal_fsync_seconds_count"] == n
    assert d["zipkin_wal_last_seq"] == d["zipkin_wal_durable_seq"] == n
    assert d["zipkin_store_jit_compiles_total"] == dev.compile_count()
    if depth:
        assert d["zipkin_store_pipeline_encode_seconds_count"] == n
        assert d["zipkin_store_pipeline_stage_seconds_count"] == n
        assert d["zipkin_store_pipeline_commit_seconds_count"] == n
        assert d["zipkin_store_pipeline_stall_seconds_total"] >= 0.0
    # every sum is a time: none negative, the call holds its parts
    assert all(v >= 0 for k, v in d.items() if k.endswith("_sum"))
