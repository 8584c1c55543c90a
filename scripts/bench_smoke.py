"""CPU-runnable structure smoke: one JSON line of counts and identities.

The question this script answers, on any backend, every CI run: how
many ops does the fused ingest step lower to, and are the duplicate
paths bitwise equal. It reports counts (the StableHLO scatter/gather/
sort census, recompiles, launches, records, bytes) and identities
(pipelined == serial, replay == oracle, replica == primary, paged ==
ring), one phase per subsystem, each gated by its own case of
tests/test_bench_smoke.py. It reports NO time, rate or overhead: a CPU
run is no evidence for those (ROADMAP.md north star). How fast the
served path is on the chip is ``benchmark/``'s question; what one step
costs on the device is ``scripts/step_time.py``'s.

Per-kernel overhead dominates on the target device class (NOTES_r03
§3), so the SCATTER COUNT of the compiled step is the portable proxy
for its TPU cost, and the tier-1 lane asserts it doesn't creep back up.

Usage:  python scripts/bench_smoke.py [--spans 7000] [--k 8]
Emits exactly one JSON line on stdout.
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def _count_ops(stablehlo_text: str) -> dict:
    """Scatter/gather/sort census of a jitted function's StableHLO
    lowering — backend-INDEPENDENT (the CPU backend fuses scatters out
    of its optimized HLO, so compiled-module counts aren't portable),
    and exactly the structural quantity the unified-arena work drove
    down: how many scatter/sort ops the ingest step ISSUES per batch.
    r5 split-design baseline at the smoke shapes: 101 scatters /
    6 sorts / 80 gathers; the r6 unified arena shipped 95 / 5 / 79;
    the r12 counting-sort rank path shipped 95 / 4 / 79; the PR 26
    arena planes shipped 95 / 4 / 84; the PR 30 ring windows ship
    54 / 4 / 84 (ceilings
    centralized in zipkin_tpu.store.census — the one place the tier-1
    gate reads them from). One shared counter (dev.
    stablehlo_op_census) backs this gate AND the runtime
    TpuSpanStore.step_census observable, so they can never drift."""
    from zipkin_tpu.store.device import stablehlo_op_census

    return stablehlo_op_census(stablehlo_text)


def run_archive() -> dict:
    """Cold-tier phase: capture -> compact -> cold query, with the
    memory store as the identity oracle. Proves on every CI run that
    (a) eviction capture adds ZERO ops to the fused ingest step (its
    lowering census with a sink attached equals the plain store's),
    (b) a 4x-ring ingest leaves every evicted span answerable, and
    (c) zone-map pruning actually skips segments."""
    import numpy as np

    from zipkin_tpu.columnar.schema import SpanBatch
    from zipkin_tpu.store import device as dev
    from zipkin_tpu.store.archive import ArchiveParams, TieredSpanStore
    from zipkin_tpu.store.memory import InMemorySpanStore
    from zipkin_tpu.store.tpu import TpuSpanStore
    from zipkin_tpu.tracegen import generate_traces

    config = dev.StoreConfig(
        capacity=1 << 8, ann_capacity=1 << 10, bann_capacity=1 << 9,
        max_services=32, max_span_names=64, max_annotation_values=256,
        max_binary_keys=64, cms_width=1 << 10, hll_p=6,
        quantile_buckets=256,
    )
    n_spans = 4 * config.capacity
    traces = generate_traces(n_traces=n_spans // 4, max_depth=3,
                             n_services=8)
    spans = [s for t in traces for s in t][:n_spans]
    chunk = 128

    plain = TpuSpanStore(config)  # no sink: the census's other side
    hot = TpuSpanStore(config)
    tiered = TieredSpanStore(hot, params=ArchiveParams.for_config(
        config, compact_fanin=2, small_span_limit=config.capacity,
        bloom_bits=1 << 12, cms_width=1 << 10, hll_p=6,
    ))
    oracle = InMemorySpanStore()
    for i in range(0, len(spans), chunk):
        tiered.apply(spans[i:i + chunk])
    oracle.apply(spans)

    # The fused step's lowering with the sink ATTACHED — must census
    # identically to the plain store's (capture is a separate launch).
    db = dev.make_device_batch(
        SpanBatch.empty(0, 0, 0),
        name_lc_id=np.zeros(0, np.int32),
        indexable=np.zeros(0, bool),
        pad_spans=256, pad_anns=512, pad_banns=256,
    )
    ops_plain = _count_ops(
        dev.ingest_step.lower(plain.state, db).as_text())
    ops_tiered = _count_ops(
        dev.ingest_step.lower(hot.state, db).as_text())

    # Identity vs oracle across the whole history (incl. evicted).
    tids = sorted({s.trace_id for s in spans})
    sample = tids[:4] + tids[len(tids) // 2:len(tids) // 2 + 4] \
        + tids[-4:]
    end_ts = 1 << 60
    fetch_ok = all(
        tiered.get_spans_by_trace_ids([t])
        == oracle.get_spans_by_trace_ids([t]) for t in sample
    )
    svc = sorted(oracle.get_all_service_names())[0]
    ids_ok = (
        tiered.get_trace_ids_by_name(svc, None, end_ts, 10 * n_spans)
        == oracle.get_trace_ids_by_name(svc, None, end_ts,
                                        10 * n_spans)
    )
    dur_ok = (tiered.get_traces_duration(sample)
              == oracle.get_traces_duration(sample))
    pruned0 = tiered.archive.c_pruned.value
    first_ts = min(s.first_timestamp for s in spans
                   if s.first_timestamp is not None)
    tiered.get_trace_ids_by_name(svc, None, first_ts + 1, 4)
    c = tiered.counters()
    return {
        "spans": len(spans),
        "segments_written": int(c["archive_segments_written"]),
        "compactions": int(c["archive_compactions"]),
        "segments_pruned": int(
            tiered.archive.c_pruned.value - pruned0),
        "cold_spans": int(c["archive_cold_spans"]),
        "cold_compression_ratio": round(
            c["archive_cold_raw_bytes"]
            / max(c["archive_cold_bytes"], 1.0), 2),
        "identical": bool(fetch_ok and ids_ok and dur_ok),
        "step_census_with_capture": ops_tiered,
        "step_census_plain": ops_plain,
    }


def run_pipeline(depth: int = 4) -> dict:
    """Pipelined-ingest phase: the same spans driven through the
    serial write path (inline capture sealing) and through the
    three-stage pipeline (async sealer), proving on every CI run that
    (a) the pipelined drive lands a BITWISE identical device state and
    an identical cold tier, (b) a warmed pipeline performs ZERO jit
    recompiles (pow2 staging buckets only hit cached entries), (c)
    H2D staging adds zero ops to the fused step's lowering, and (d)
    ingest never stalled on capture sealing (stall counter stays 0 at
    a generous backlog — deliberate backpressure is exercised in
    tests/test_pipeline.py instead)."""
    import jax
    import numpy as np

    from zipkin_tpu.columnar.schema import SpanBatch
    from zipkin_tpu.store import device as dev
    from zipkin_tpu.store.archive import ArchiveParams, TieredSpanStore
    from zipkin_tpu.store.tpu import TpuSpanStore
    from zipkin_tpu.tracegen import generate_traces

    # Same geometry as run_archive so this phase reuses its jit cache.
    config = dev.StoreConfig(
        capacity=1 << 8, ann_capacity=1 << 10, bann_capacity=1 << 9,
        max_services=32, max_span_names=64, max_annotation_values=256,
        max_binary_keys=64, cms_width=1 << 10, hll_p=6,
        quantile_buckets=256,
    )
    # 2 ring turns: enough to lap the ring and seal several capture
    # windows, at half the archive phase's drive cost.
    n_spans = 2 * config.capacity
    traces = generate_traces(n_traces=n_spans // 4, max_depth=3,
                             n_services=8)
    spans = [s for t in traces for s in t][:n_spans]
    chunk = 128

    def build(backlog):
        hot = TpuSpanStore(config)
        hot.capture_backlog = backlog
        return hot, TieredSpanStore(hot, params=ArchiveParams.for_config(
            config, compact_fanin=2, small_span_limit=config.capacity,
            bloom_bits=1 << 12, cms_width=1 << 10, hll_p=6,
        ))

    def drive(tiered):
        for i in range(0, len(spans), chunk):
            tiered.apply(spans[i:i + chunk])

    # Warm every jit the gated PIPELINED drive will hit (ingest,
    # sweep, bucket close, capture — staged device-resident arguments
    # key their own jit cache rows, distinct from host-numpy ones, see
    # dev.stage_batch), so the recompile gate below is a true
    # steady-state zero. Nothing is gated on the serial side's
    # compiles.
    warm_ph, warm_pt = build(64)
    warm_ph.start_pipeline(depth)
    drive(warm_pt)
    warm_ph.drain_pipeline()
    warm_pt.close()

    serial_hot, serial_t = build(0)
    drive(serial_t)

    pipe_hot, pipe_t = build(64)
    compiles0 = dev.compile_count()
    pipe_hot.start_pipeline(depth)
    drive(pipe_t)
    pipe_hot.drain_pipeline()
    pipe_hot.seal_barrier()
    recompiles = dev.compile_count() - compiles0
    pipe_hot.stop_pipeline()
    sealer = pipe_hot._sealer
    capture_stall_s = float(sealer.c_stall.value) if sealer else 0.0

    flat_a, _ = jax.tree_util.tree_flatten(serial_hot.state)
    flat_b, _ = jax.tree_util.tree_flatten(pipe_hot.state)
    identical = len(flat_a) == len(flat_b) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(flat_a, flat_b)
    )
    cs, cp = serial_t.counters(), pipe_t.counters()
    identical = (identical
                 and cs["archive_cold_spans"] == cp["archive_cold_spans"]
                 and cs["archive_segments_written"]
                 == cp["archive_segments_written"])

    # Staging must be invisible to the compiler: the fused step lowers
    # IDENTICALLY from device_put-staged arrays and host numpy arrays.
    db = dev.make_device_batch(
        SpanBatch.empty(0, 0, 0), name_lc_id=np.zeros(0, np.int32),
        indexable=np.zeros(0, bool),
        pad_spans=256, pad_anns=512, pad_banns=256,
    )
    ops_host = _count_ops(
        dev.ingest_step.lower(serial_hot.state, db).as_text())
    ops_staged = _count_ops(
        dev.ingest_step.lower(pipe_hot.state,
                              dev.stage_batch(db)).as_text())
    serial_t.close()
    pipe_t.close()
    return {
        "spans": len(spans),
        "depth": depth,
        "capture_stall_s": round(capture_stall_s, 4),
        "windows_sealed": int(sealer.c_sealed.value) if sealer else 0,
        "recompiles_after_warmup": int(recompiles),
        "identical": bool(identical),
        "staging_census_equal": ops_host == ops_staged,
    }


def run_wal() -> dict:
    """Durability phase (r10 tentpole): the same spans driven through
    a plain store (the uncrashed oracle) and through a WAL-attached
    store at the group-commit default, proving on every CI run that
    (a) a full-log replay into a fresh store lands a BITWISE identical
    device state (the ack-after-append contract's other half: what was
    journaled is exactly what recovery rebuilds) and (b) journaling
    adds ZERO jit recompiles in steady state and replay adds zero more
    (replay re-pads through the same pow2 buckets the drive compiled).
    What the append costs is a question for the chip: ROADMAP.md S11's
    WAL on/off pair of cells."""
    import os
    import shutil
    import tempfile

    from zipkin_tpu.store import device as dev
    from zipkin_tpu.store.tpu import TpuSpanStore
    from zipkin_tpu.testing.crash import states_bitwise_equal
    from zipkin_tpu.tracegen import generate_traces
    from zipkin_tpu.wal import WriteAheadLog, recover

    config = dev.StoreConfig(
        capacity=1 << 10, ann_capacity=1 << 12, bann_capacity=1 << 11,
        max_services=32, max_span_names=128, max_annotation_values=256,
        max_binary_keys=64, cms_width=1 << 10, hll_p=8,
        quantile_buckets=512,
    )
    traces = generate_traces(n_traces=2000, max_depth=3, n_services=16)
    spans = [s for t in traces for s in t][:5000]
    chunk = 128

    def drive(store):
        for i in range(0, len(spans), chunk):
            store.apply(spans[i:i + chunk])
        return store

    root = tempfile.mkdtemp(prefix="wal-smoke-")
    try:
        # The no-WAL drive is the oracle AND the jit warm-up: the
        # journaled drive after it must compile nothing.
        oracle = drive(TpuSpanStore(config))
        compiles0 = dev.compile_count()
        s_int = TpuSpanStore(config)
        wal_dir = os.path.join(root, "wal-interval")
        s_int.attach_wal(WriteAheadLog(wal_dir, fsync="interval"))
        drive(s_int)
        steady_recompiles = dev.compile_count() - compiles0

        wal_stats = s_int.wal.stats()
        s_int.wal.sync()
        s_int.wal.close()

        # Full-log replay into a FRESH store == the uncrashed oracle.
        compiles1 = dev.compile_count()
        wal2 = WriteAheadLog(wal_dir, fsync="off")
        rec, rstats = recover(
            None, wal2, fresh_store=lambda: TpuSpanStore(config))
        replay_recompiles = dev.compile_count() - compiles1
        identical = states_bitwise_equal(oracle.state, rec.state)
        wal2.close()
        return {
            "spans": len(spans),
            "steady_state_recompiles": int(steady_recompiles),
            "replay_recompiles": int(replay_recompiles),
            "replay_identical": bool(identical),
            "replayed_records": rstats["replayed_records"],
            "wal_bytes_per_span": round(
                wal_stats["wal_bytes"] / len(spans), 1),
            "wal_segments": wal_stats["wal_segments"],
        }
    finally:
        shutil.rmtree(root, ignore_errors=True)


def run_query() -> dict:
    """Resident-query-engine phase (r11 tentpole): the three-tier read
    path (query/engine.py) proven structurally on every CI run:
    (a) sketch-tier answers (catalogs, quantiles, top-k, HLL) are
    IDENTICAL to the device read path's while costing zero device
    round-trips;
    (b) the steady-state query loop performs ZERO jit recompiles (the
    resident programs stay resident); (c) a cache hit returns answers
    bitwise-equal to the cold computation, and an ingest commit
    invalidates precisely (the frontier-keyed re-answer matches a
    fresh store read). What a tier costs is a question for the chip
    (no cell reads yet: ROADMAP.md S8)."""
    from zipkin_tpu import obs
    from zipkin_tpu.query.engine import QueryEngine
    from zipkin_tpu.store import device as dev
    from zipkin_tpu.store.tpu import TpuSpanStore
    from zipkin_tpu.tracegen import generate_traces

    config = dev.StoreConfig(
        capacity=1 << 10, ann_capacity=1 << 12, bann_capacity=1 << 11,
        max_services=32, max_span_names=128, max_annotation_values=256,
        max_binary_keys=64, cms_width=1 << 10, hll_p=8,
        quantile_buckets=512,
    )
    traces = generate_traces(n_traces=1200, max_depth=3, n_services=16)
    spans = [s for t in traces for s in t][:3000]
    store = TpuSpanStore(config)
    for i in range(0, len(spans), 128):
        store.apply(spans[i:i + 128])
    reg = obs.Registry()
    engine = QueryEngine(store, window_s=0.0, registry=reg)
    svcs = sorted(store.get_all_service_names())
    qs = [0.5, 0.95, 0.99]

    # Sketch-tier identity: every answer bitwise-equals the device
    # read path's (the conformance half of the sketch-tier contract).
    ident = engine.get_all_service_names() == store.get_all_service_names()
    for s in svcs:
        ident = ident and (
            engine.get_span_names(s) == store.get_span_names(s)
            and engine.service_duration_quantiles(s, qs)
            == store.service_duration_quantiles(s, qs)
            and engine.top_annotations(s) == store.top_annotations(s)
            and engine.top_binary_keys(s) == store.top_binary_keys(s)
        )
    ident = ident and (engine.estimated_unique_traces()
                       == store.estimated_unique_traces())

    end_ts = max(s.last_timestamp for s in spans if s.last_timestamp) + 1
    queries = [("name", s, None, end_ts, 10) for s in svcs[:8]]
    engine.executor.run(queries)  # warm the multi-probe jit rows

    # Steady state: sketch + index loops must add ZERO compiles —
    # across the ingest jits AND the resident query programs
    # (dev.query_compile_count, the kernels the executor dispatches).
    compiles0 = dev.compile_count() + dev.query_compile_count()
    for _ in range(40):
        engine.service_duration_quantiles(svcs[0], qs)
        engine.top_annotations(svcs[1 % len(svcs)])
        engine.get_all_service_names()
    for _ in range(20):
        engine.executor.run(queries)  # cache-bypassing resident path
    recompiles = (dev.compile_count() + dev.query_compile_count()
                  - compiles0)

    # Cache: hit answers bitwise-equal to the cold computation, and an
    # ingest commit invalidates precisely (frontier advance).
    def ids(rows):
        return [[(i.trace_id, i.timestamp) for i in r] for r in rows]

    hits0 = engine.c_hits.value
    cold = ids(engine.get_trace_ids_multi(queries))
    warm = ids(engine.get_trace_ids_multi(queries))
    cache_hit_ok = (warm == cold
                    and engine.c_hits.value - hits0 >= len(queries))
    store.apply(spans[:256])  # frontier advances
    after = ids(engine.get_trace_ids_multi(queries))
    fresh = ids(store.get_trace_ids_multi(queries))
    invalidation_ok = after == fresh
    return {
        "spans": len(spans),
        "sketch_identical": bool(ident),
        "steady_recompiles": int(recompiles),
        "cache_hit_identical": bool(cache_hit_ok),
        "cache_invalidation_exact": bool(invalidation_ok),
        "cache_hits": int(engine.c_hits.value),
        "cache_misses": int(engine.c_misses.value),
        "sketch_answers": int(engine.c_sketch.value),
    }


def run_ingest_structure() -> dict:
    """Ingest-structure phase (r12 tentpole): the structural claims
    behind the batch-escalation and counting-sort work, proven on
    every CI run:

    (a) the counting-sort rank path's fused-step lowering carries
        strictly fewer stablehlo.sort ops than the argsort path's (the
        portable proxy for the deleted O(N log N) entry cost) while
        issuing no extra scatters/gathers — store-level bitwise
        identity between the two paths is the fuzz suite's job
        (tests/test_rank_paths.py), not re-driven here;
    (b) a batch-escalated geometry (StoreConfig.batch_spans) driven
        through the three-stage pipeline performs ZERO steady-state
        jit recompiles once warmed — escalation changes pad buckets,
        not compile-cache churn;
    (c) every ring of the ring layout is written as a window."""
    import numpy as np

    from zipkin_tpu.store import device as dev
    from zipkin_tpu.store.tpu import TpuSpanStore
    from zipkin_tpu.tracegen import generate_traces
    from zipkin_tpu.columnar.schema import SpanBatch

    base = dict(
        capacity=1 << 10, ann_capacity=1 << 12, bann_capacity=1 << 11,
        max_services=32, max_span_names=128, max_annotation_values=256,
        max_binary_keys=64, cms_width=1 << 10, hll_p=8,
        quantile_buckets=512,
    )
    cfg_arg = dev.StoreConfig(**base, rank_path="argsort",
                              batch_spans=256)
    cfg_cnt = dev.StoreConfig(**base, rank_path="counting",
                              batch_spans=256)
    # The escalated batch geometry: same store, bigger launches (the
    # ring guards clamp at capacity//2 = 512 — this IS the escalation
    # ceiling for the smoke ring).
    cfg_big = dev.StoreConfig(**base, rank_path="counting",
                              batch_spans=512)
    traces = generate_traces(n_traces=440, max_depth=3, n_services=16)
    spans = [s for t in traces for s in t][:1280]

    def drive(store, slice_spans=512):
        for i in range(0, len(spans), slice_spans):
            store.apply(spans[i:i + slice_spans])
        return store

    # Per-path census: lowering only — the trace also records each
    # config's active rank path (dev.active_paths), no drive needed.
    db = dev.make_device_batch(
        SpanBatch.empty(0, 0, 0), name_lc_id=np.zeros(0, np.int32),
        indexable=np.zeros(0, bool),
        pad_spans=256, pad_anns=1024, pad_banns=512,
    )
    census_arg = _count_ops(
        dev.ingest_step.lower(dev.init_state(cfg_arg), db).as_text())
    census_cnt = _count_ops(
        dev.ingest_step.lower(dev.init_state(cfg_cnt), db).as_text())

    # Batch escalation through the pipeline: warm the escalated
    # geometry end-to-end (staged device args key their own jit rows),
    # then gate steady-state recompiles at ZERO across a fresh
    # pipelined drive of the same geometry.
    warm = TpuSpanStore(cfg_big)
    warm.start_pipeline(4)
    drive(warm)
    warm.drain_pipeline()
    warm.stop_pipeline()
    meas = TpuSpanStore(cfg_big)
    compiles0 = dev.compile_count()
    meas.start_pipeline(4)
    drive(meas)
    meas.drain_pipeline()
    recompiles = dev.compile_count() - compiles0
    meas.stop_pipeline()
    c_meas = meas.counters()
    warm.close()
    meas.close()

    return {
        "spans": len(spans),
        "census_argsort": census_arg,
        "census_counting": census_cnt,
        "rank_path_argsort_cfg": dev.active_paths(cfg_arg).get(
            "rank", ()),
        "rank_path_counting_cfg": dev.active_paths(cfg_cnt).get(
            "rank", ()),
        "rank_path_counting": c_meas["rank_path_counting"],
        "ring_write_cfg": dev.active_paths(cfg_cnt).get("ring_write", ()),
        "ring_write_window": c_meas["ring_write_window"],
        "batch_spans_geometries": [cfg_cnt.batch_spans,
                                   cfg_big.batch_spans],
        "escalated_batch_spans_limit": c_meas["batch_spans_limit"],
        "recompiles_after_batch_escalation": int(recompiles),
    }


def run_windows() -> dict:
    """Windowed-analytics phase (r13 tentpole), tier-1 gates:

    (a) census arithmetic — the windowed arena's fused-step cost is
        EXACTLY the gated bump (census.MAX_STEP_* = BASE + WINDOW_BUMP
        with the window on; the window-off lowering at the BASE
        counts, which is also the library-default lowering the main
        stream gates), so the feature can't silently grow;
    (b) mirror-vs-device BITWISE identity of all four window arrays
        after a multi-bucket drive (incl. error spans), serial AND
        pipelined;
    (c) zero steady-state recompiles with the window update fused
        (same drive twice through warmed shapes);
    (d) the sketch-tier windowed reads (quantiles / burn / heatmap)
        answer with zero device dispatches and the quantile lands
        inside the documented solver rank tolerance vs the exact span
        durations."""
    import numpy as np

    import jax

    from zipkin_tpu.aggregate import windows as win
    from zipkin_tpu.models.span import Annotation, Endpoint, Span
    from zipkin_tpu.store import device as dev
    from zipkin_tpu.store.tpu import TpuSpanStore

    cfg = dev.StoreConfig(
        capacity=1 << 10, ann_capacity=1 << 12, bann_capacity=1 << 11,
        max_services=32, max_span_names=128, max_annotation_values=256,
        max_binary_keys=64, cms_width=1 << 10, hll_p=8,
        quantile_buckets=512, rank_path="counting",
        window_seconds=60, window_buckets=8,
    )
    rng = np.random.default_rng(42)
    eps = [Endpoint(1 + i, 80, f"wsvc{i}") for i in range(4)]
    base = 1_700_000_000_000_000

    def gen(n, seed_off=0):
        out = []
        for i in range(n):
            ep = eps[(i + seed_off) % 4]
            t0 = base + int(rng.integers(0, 5 * 60_000_000))
            d = int(rng.lognormal(7.0, 1.3)) + 1
            anns = [Annotation(t0, "sr", ep),
                    Annotation(t0 + d, "ss", ep)]
            if i % 9 == 0:
                anns.append(Annotation(t0 + 1, "error", ep))
            out.append(Span(i // 3 + 1, f"wop{i % 4}", i + 1, None,
                            tuple(anns), ()))
        return out

    spans = gen(600)

    def drive(store, pipelined):
        if pipelined:
            store.start_pipeline(4)
        for i in range(0, len(spans), 200):
            store.apply(spans[i:i + 200])
        if pipelined:
            store.drain_pipeline()
            store.stop_pipeline()

    def win_state(store):
        st = store.state
        return jax.device_get(
            (st.win_epoch, st.win_counts, st.win_sums, st.win_mm))

    serial = TpuSpanStore(cfg)
    drive(serial, False)
    piped = TpuSpanStore(cfg)
    drive(piped, True)
    dev_arrays = win_state(serial)
    mir = serial.sketch_mirror
    mirror_bitwise = all(
        np.array_equal(a, b) for a, b in zip(
            dev_arrays,
            (mir.win_epoch, mir.win_counts, mir.win_sums, mir.win_mm)))
    piped_bitwise = all(
        np.array_equal(a, b)
        for a, b in zip(dev_arrays, win_state(piped)))

    # (c) zero steady-state recompiles across a re-drive of warmed
    # shapes with the window update fused into the step.
    compiles0 = dev.compile_count()
    redrive = TpuSpanStore(cfg)
    drive(redrive, False)
    recompiles = dev.compile_count() - compiles0

    # (d) sketch-tier reads — pure host math; gate the solver's rank.
    est = serial.windowed_quantiles("wsvc1", [0.5, 0.99])
    burn = serial.slo_burn("wsvc1", objective=0.99)
    heat = serial.latency_heatmap("wsvc1", bands=6)
    durs = np.sort([
        s.duration for s in spans
        if (s.service_name or "") == "wsvc1" and s.duration is not None
    ])
    rank_err = (abs(np.searchsorted(durs, est[0])
                    / max(len(durs) - 1, 1) - 0.5)
                if est else float("inf"))

    # (a) census arithmetic: window-on vs window-off lowerings.
    from zipkin_tpu.columnar.schema import SpanBatch

    db = dev.make_device_batch(
        SpanBatch.empty(0, 0, 0), name_lc_id=np.zeros(0, np.int32),
        indexable=np.zeros(0, bool),
        pad_spans=256, pad_anns=1024, pad_banns=512,
    )
    census_on = _count_ops(
        dev.ingest_step.lower(dev.init_state(cfg), db).as_text())
    census_off = _count_ops(dev.ingest_step.lower(
        dev.init_state(cfg._replace(window_seconds=0)), db).as_text())

    for s in (serial, piped, redrive):
        s.close()
    return {
        "census_window_on": census_on,
        "census_window_off": census_off,
        "mirror_bitwise": bool(mirror_bitwise),
        "pipelined_bitwise": bool(piped_bitwise),
        "recompiles_steady_state": int(recompiles),
        "quantile_rank_err": round(float(rank_err), 4),
        "solver_rank_tol": win.SOLVER_RANK_TOL,
        "burn_total": burn["windows"][0]["total"],
        "burn_errors": burn["windows"][0]["errors"],
        "heatmap_columns": len(heat["bucketStartsTs"]),
        "window_spans_folded": int(mir.win_spans_total),
        "window_errors_folded": int(mir.win_errors_total),
    }


def run_paged() -> dict:
    """Paged-layout phase (r19 tentpole), tier-1 gates:

    (a) census arithmetic — the paged fused-step lowering costs
        EXACTLY the gated bump (census.expected_census("+PAGED"); the
        ring lowering stays at BASE), so the layout can't silently
        grow the step;
    (b) ring-vs-paged BITWISE query parity on a skewed (zipf trace
        size) stream — per-trace reads AND id lookups answer
        identically through both layouts;
    (c) zero steady-state recompiles driving the paged layout through
        the ingest pipeline (same stream twice through warmed shapes).
    Whether the layout retains more per byte, and at what rate, is not
    measured: no cell runs it (ROADMAP.md D3)."""
    import numpy as np

    import jax  # noqa: F401 — device_get via stores below

    from zipkin_tpu.models.span import Annotation, Endpoint, Span
    from zipkin_tpu.store import census
    from zipkin_tpu.store import device as dev
    from zipkin_tpu.store.tpu import TpuSpanStore

    cfg_ring = dev.StoreConfig(
        capacity=1 << 10, ann_capacity=1 << 12, bann_capacity=1 << 11,
        max_services=32, max_span_names=128, max_annotation_values=256,
        max_binary_keys=64, cms_width=1 << 10, hll_p=8,
        quantile_buckets=512, rank_path="counting",
    )
    cfg_paged = cfg_ring._replace(layout="paged", page_rows=128)

    # Skewed stream: zipf trace sizes, 1-span polls to 64-span batch
    # traces interleaved — the shape the paged layout exists for.
    rng = np.random.default_rng(7)
    eps = [Endpoint(1 + i, 80, f"psvc{i}") for i in range(4)]
    base = 1_700_000_000_000_000
    spans = []
    tid = 1
    while len(spans) < 700:
        size = min(int(rng.zipf(1.6)), 64)
        ep = eps[tid % 4]
        for j in range(size):
            t0 = base + tid * 1000 + j
            spans.append(Span(
                tid, f"pop{j % 4}", tid * 1000 + j + 1, None,
                (Annotation(t0, "sr", ep),
                 Annotation(t0 + 7, "ss", ep)), ()))
        tid += 1
    tids = list(range(1, tid))
    end_ts = base + tid * 1000 + 10_000

    def drive(store, pipelined=False):
        if pipelined:
            store.start_pipeline(4)
        for i in range(0, len(spans), 200):
            store.apply(spans[i:i + 200])
        if pipelined:
            store.drain_pipeline()
            store.stop_pipeline()

    ring = TpuSpanStore(cfg_ring)
    drive(ring)
    paged = TpuSpanStore(cfg_paged)
    drive(paged)

    # (b) bitwise parity: whole-trace reads and id lookups. One
    # batched sweep covers every trace (one launch per store); the
    # single-trace path is sampled — per-tid exhaustion lives in
    # tests/test_paged.py's slow lane.
    parity = (
        ring.get_spans_by_trace_ids(tids)
        == paged.get_spans_by_trace_ids(tids)) and all(
        ring.get_spans_by_trace_ids([t]) ==
        paged.get_spans_by_trace_ids([t])
        for t in tids[::8])
    key = lambda x: (x.trace_id, x.timestamp)  # noqa: E731
    ids_parity = all(
        sorted(ring.get_trace_ids_by_name(f"psvc{i}", None, end_ts,
                                          200), key=key)
        == sorted(paged.get_trace_ids_by_name(f"psvc{i}", None, end_ts,
                                              200), key=key)
        for i in range(4))

    # (c) zero steady-state recompiles through the pipeline: warm the
    # pipelined (device-staged) jit shapes by re-driving the already
    # -compared paged store, then a FRESH store must compile nothing.
    drive(paged, pipelined=True)
    compiles0 = dev.compile_count()
    steady = TpuSpanStore(cfg_paged)
    drive(steady, pipelined=True)
    recompiles = dev.compile_count() - compiles0

    # (a) census arithmetic: paged-on vs ring lowering at the smoke
    # shapes — exact equality against the lowering table rows.
    census_on = steady.step_census(256, 1024, 512)
    census_off = ring.step_census(256, 1024, 512)
    es, eo, eg = census.expected_census("+PAGED")
    bs, bo, bg = census.expected_census()

    pstats = steady.counters()
    for s in (ring, paged, steady):
        s.close()
    return {
        "census_paged_on": census_on,
        "census_paged_off": census_off,
        "census_expected_on": {"scatter": es, "sort": eo, "gather": eg},
        "census_expected_off": {"scatter": bs, "sort": bo,
                                "gather": bg},
        "query_parity_bitwise": bool(parity),
        "ids_parity_bitwise": bool(ids_parity),
        "recompiles_steady_state": int(recompiles),
        "pages_active": int(pstats["pages_active"]),
        "pages_free": int(pstats["pages_free"]),
        "page_reclaims_total": int(pstats["page_reclaims_total"]),
    }


def run_replication() -> dict:
    """WAL-shipped replication phase (r15 tentpole), proven
    structurally on every CI run: (a) a device-free ReplicaSpanStore
    fed only shipped WAL records over the real framed-TCP ship path
    answers the sketch tier BITWISE identical to the primary at the
    same applied frontier (mirror arrays equal element-for-element;
    catalog/quantile/top-k/HLL/trace-read answers equal) — while
    performing ZERO jit compiles (it is device-free by construction,
    and the warm standby replays into already-compiled shapes);
    (b) a warm standby fed the same stream lands a state bitwise equal
    to the primary's and can be promoted (how long a failover takes is
    the chip's question: ROADMAP.md R6, `crash-recover`); (c) the
    follower kept its lag bounded under full ingest load and caught up to lag 0 at the drained frontier, with the
    un-fetched tail pinned against truncation by its cursor."""
    import os  # noqa: F401 — tempdir cleanup below
    import shutil
    import tempfile

    from zipkin_tpu.replicate import (
        Follower,
        ReplicaTarget,
        ShipClient,
        ShipServer,
        StandbyTarget,
        WalShipper,
    )
    from zipkin_tpu.store import device as dev
    from zipkin_tpu.store.archive import TieredSpanStore
    from zipkin_tpu.store.replica import ReplicaSpanStore
    from zipkin_tpu.store.tpu import TpuSpanStore
    from zipkin_tpu.testing.crash import states_bitwise_equal
    from zipkin_tpu.tracegen import generate_traces
    from zipkin_tpu.wal import WriteAheadLog

    # The run_wal geometry — the ingest-step compiles are shared, so
    # this phase's primary AND standby drives hit warm jit caches.
    config = dev.StoreConfig(
        capacity=1 << 10, ann_capacity=1 << 12, bann_capacity=1 << 11,
        max_services=32, max_span_names=128, max_annotation_values=256,
        max_binary_keys=64, cms_width=1 << 10, hll_p=8,
        quantile_buckets=512,
    )
    # 2560 = 20 aligned chunks: enough to lap the 1<<10 ring several
    # times (captures + cold segments on both drives) while keeping
    # the phase's three drives inside the tier-1 wall budget.
    traces = generate_traces(n_traces=2000, max_depth=3, n_services=16)
    spans = [s for t in traces for s in t][:2560]
    chunk = 128
    root = tempfile.mkdtemp(prefix="replication-smoke-")
    server = None
    followers = []
    stores = []
    try:
        # Warm-up: the EXACT stream through an identical (discarded)
        # tiered store compiles every pad bucket and capture-window
        # variant the real drive will hit, so the compile-count delta
        # below is attributable to replication alone.
        warm = TieredSpanStore(TpuSpanStore(config))
        for i in range(0, len(spans), chunk):
            warm.apply(spans[i:i + chunk])

        primary = TieredSpanStore(TpuSpanStore(config))
        wal = WriteAheadLog(os.path.join(root, "wal"), fsync="off")
        primary.attach_wal(wal)
        shipper = WalShipper(primary)
        server = ShipServer(shipper, host="127.0.0.1", port=0)
        port = server.server_address[1]
        server.serve_in_thread()

        # Chunk-ALIGNED split: a half boundary off the chunk grid would
        # shift every second-half chunk boundary off the warm drive's
        # (different ann-count pads -> a spurious "recompile").
        half = (len(spans) // 2 // chunk) * chunk
        for i in range(0, half, chunk):
            primary.apply(spans[i:i + chunk])
        compiles0 = dev.compile_count() + dev.query_compile_count()

        rc = ShipClient("127.0.0.1", port, "smoke-replica",
                        mode="replica")
        replica = ReplicaSpanStore(dev.config_from_dict(
            rc.connect()["config"]))
        stores.append(replica)
        f_rep = Follower(ReplicaTarget(replica), rc,
                         poll_interval_s=0.002).start()
        followers.append(f_rep)
        sc = ShipClient("127.0.0.1", port, "smoke-standby",
                        mode="standby")
        sc.connect()
        standby = TpuSpanStore(config)
        f_sby = Follower(StandbyTarget(standby), sc,
                         poll_interval_s=0.002).start()
        followers.append(f_sby)

        # Load phase: keep ingesting while the followers stream.
        max_lag = 0
        for i in range(half, len(spans), chunk):
            primary.apply(spans[i:i + chunk])
            max_lag = max(max_lag, f_rep.lag_records())
        wal.sync()
        # Failover: the standby applies the remaining durable tail and
        # is promoted.
        sby_up = f_sby.drain(60.0)
        promoted = f_sby.promote()
        rep_up = f_rep.drain(60.0)
        caught_up = sby_up and rep_up
        standby_bitwise = states_bitwise_equal(
            primary.hot.state, promoted.state)
        # Measured HERE — after the whole replication stream applied
        # but before the agreement reads (the primary's query kernels
        # compile on their first use in this geometry; those are read
        # compiles, not replication's).
        replication_compiles = (dev.compile_count()
                                + dev.query_compile_count()
                                - compiles0)

        # Replica agreement at the drained frontier.
        hot = primary.hot
        a_p = hot.ensure_sketch_mirror().arrays()
        a_r = replica.sketch_mirror.arrays()
        import numpy as np

        mirror_bitwise = all(
            np.array_equal(x, y) for x, y in zip(a_p, a_r))
        svcs = sorted(primary.get_all_service_names())
        end_ts = 1 << 62
        tids = sorted({s.trace_id for s in spans[::97]})[:24]
        agree = replica.get_all_service_names() == set(svcs)
        for svc in svcs[:4]:
            agree &= (replica.service_duration_quantiles(
                svc, [0.5, 0.95, 0.99])
                == primary.service_duration_quantiles(
                    svc, [0.5, 0.95, 0.99]))
            agree &= (replica.top_annotations(svc)
                      == primary.top_annotations(svc))
            agree &= (replica.top_binary_keys(svc)
                      == primary.top_binary_keys(svc))
            agree &= (replica.get_trace_ids_by_name(
                svc, None, end_ts, 10)
                == primary.get_trace_ids_by_name(svc, None, end_ts,
                                                 10))
        agree &= (replica.estimated_unique_traces()
                  == primary.estimated_unique_traces())
        agree &= (replica.get_spans_by_trace_ids(tids)
                  == primary.get_spans_by_trace_ids(tids))
        agree &= (replica.traces_exist(tids)
                  == primary.traces_exist(tids))
        agree &= (replica.get_traces_duration(tids)
                  == primary.get_traces_duration(tids))

        status = shipper.status()
        cursors = wal.cursors()
        return {
            "spans": len(spans),
            "records_shipped": int(
                status["followers"]["smoke-replica"]["shippedRecords"]),
            "shipped_bytes": int(
                status["followers"]["smoke-replica"]["shippedBytes"]),
            "replica_mirror_bitwise": bool(mirror_bitwise),
            "replica_answers_identical": bool(agree),
            "replication_recompiles": int(replication_compiles),
            "standby_bitwise": bool(standby_bitwise),
            "max_lag_records": int(max_lag),
            "caught_up": bool(caught_up),
            "follower_cursor_pinned": bool(
                cursors.get("smoke-replica", 0) >= 1),
        }
    finally:
        for f in followers:
            f.close()
        for s in stores:
            s.close()
        if server is not None:
            server.shutdown()
        shutil.rmtree(root, ignore_errors=True)


def run_sharded() -> dict:
    """Multi-chip sharded-serving phase (r16 tentpole): a 2-shard
    fleet on the host-simulated mesh must (a) fuse concurrent API
    reads through the cross-shard dispatcher into collective launches
    whose answers are BITWISE identical to serialized execution, (b)
    serve that burst with ZERO jit recompiles (the mapped kernels are
    resident; the dispatcher only changes who launches them), and (c)
    answer the fleet sketch tier bitwise against a single-device
    oracle fed the same spans — name-aligned histogram rows (the two
    codecs may assign dictionary ids in different orders; values per
    service must still match exactly) and identical HLL registers."""
    import threading

    import jax
    import numpy as np
    from jax.sharding import Mesh

    from zipkin_tpu.parallel.shard import ShardedSpanStore
    from zipkin_tpu.store import device as dev
    from zipkin_tpu.store.tpu import TpuSpanStore
    from zipkin_tpu.tracegen import generate_traces

    devs = jax.devices()
    if len(devs) < 2:
        # Standalone invocation on a true single-device backend: the
        # tier-1 lane always has the 8-device virtual mesh (conftest
        # exports XLA_FLAGS before spawning this script).
        return {"skipped": "single-device backend"}
    mesh = Mesh(np.array(devs[:2]), axis_names=("shard",))
    config = dev.StoreConfig(
        capacity=1 << 10, ann_capacity=1 << 12, bann_capacity=1 << 11,
        max_services=64, max_span_names=128, max_annotation_values=512,
        max_binary_keys=128, cms_width=1 << 10, hll_p=8,
        quantile_buckets=128,
    )
    spans = [
        s for t in generate_traces(n_traces=48, max_depth=3,
                                   n_services=16,
                                   rng=np.random.default_rng(16))
        for s in t
    ]
    # A generous micro-window: every barrier-released reader must land
    # in ONE batch even on a loaded CI host (the launch-count gate in
    # tests/test_bench_smoke.py rides on it); production deployments
    # run single-digit-ms windows (main/example.py --query-window-ms).
    store = ShardedSpanStore(mesh, config, dispatch_window_s=0.5)
    single = TpuSpanStore(config)
    try:
        store.apply(spans)
        single.apply(spans)
        svcs = sorted(store.get_all_service_names())[:4]
        end_ts = 2**62

        # Warm every kernel the burst hits, then drain the window so
        # the recompile/launch deltas below measure steady state only.
        for svc in svcs:
            store.service_duration_quantiles(svc, [0.5, 0.99])
            store.get_trace_ids_by_name(svc, None, end_ts, 10)
        store.get_trace_ids_multi(
            [("name", svc, None, end_ts, 10) for svc in svcs])
        store.dispatcher.drain()

        barrier = threading.Barrier(9)
        results: dict = {}
        errors: list = []

        def cat_worker(i, svc):
            try:
                barrier.wait()
                results[i] = store.service_duration_quantiles(
                    svc, [0.5, 0.99])
            except Exception as e:  # noqa: BLE001 — surfaced below
                errors.append(repr(e))

        def ids_worker(i, svc):
            try:
                barrier.wait()
                results[i] = [
                    (r.trace_id, r.timestamp)
                    for r in store.get_trace_ids_by_name(
                        svc, None, end_ts, 10)]
            except Exception as e:  # noqa: BLE001 — surfaced below
                errors.append(repr(e))

        threads = (
            [threading.Thread(target=cat_worker, args=(i, svcs[i]))
             for i in range(4)]
            + [threading.Thread(target=ids_worker, args=(4 + i, svcs[i]))
               for i in range(4)]
        )
        for t in threads:
            t.start()
        compiles0 = dev.compile_count()
        launches0 = store.collective_launches()
        barrier.wait()
        for t in threads:
            t.join(timeout=120.0)
        burst_launches = store.collective_launches() - launches0
        recompiles = dev.compile_count() - compiles0

        # Serialized identity: each query re-issued alone must answer
        # exactly what it answered inside the fused burst.
        identical = not errors and all(
            results[i] == store.service_duration_quantiles(
                svcs[i], [0.5, 0.99])
            for i in range(4)
        ) and all(
            results[4 + i] == [
                (r.trace_id, r.timestamp)
                for r in store.get_trace_ids_by_name(
                    svcs[i], None, end_ts, 10)]
            for i in range(4)
        )

        # Fleet sketch tier vs the single-device oracle, name-aligned.
        fleet = store.ensure_sketch_mirror()
        oracle = single.ensure_sketch_mirror()
        names = sorted(single.get_all_service_names())
        rows_ok = bool(names) and all(
            np.array_equal(
                fleet.hist_row(store.dicts.services.get(n)),
                oracle.hist_row(single.dicts.services.get(n)))
            for n in names
        )
        hll_ok = np.array_equal(fleet.hll_registers(),
                                oracle.hll_registers())
        names_ok = set(names) == set(store.get_all_service_names())

        dstats = store.dispatcher.stats()
        return {
            "shards": store.n,
            "spans": len(spans),
            "burst_reads": 8,
            "burst_launches": int(burst_launches),
            "steady_state_recompiles": int(recompiles),
            "dispatcher_batches": dstats["batches"],
            "dispatcher_launches_saved": dstats["launches_saved"],
            "identical": bool(identical),
            "errors": errors[:4],
            "fleet_hist_rows_bitwise": bool(rows_ok),
            "fleet_hll_bitwise": bool(hll_ok),
            "service_names_identical": bool(names_ok),
        }
    finally:
        store.close()


def run_fleet_obs() -> dict:
    """Fleet-observability phase (r17 tentpole), proven on every CI
    run: (a) a live primary+follower ship pair under ingest lands ONE
    causally-linked self-trace spanning encode → WAL append → fsync →
    ship → follower apply in the primary's own store, parent ids
    verified; (b) the federated ``/metrics?fleet=1`` merge carries
    both processes' samples label-distinguished with values bitwise
    identical to each process's own scrape; (c) the stall watchdog
    fires on an injected parked-fsync error and clears when the error
    does; (d) self-tracing at the production sampling cadence adds
    ZERO new device launches in steady state (compile-count delta 0,
    fused-step census equality). What it costs in ingest time is a
    question for the chip: ROADMAP.md S11's lineage on/off pair."""
    import os
    import shutil
    import tempfile

    from zipkin_tpu import obs
    from zipkin_tpu.obs import fleet as fobs
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
    from zipkin_tpu.tracegen import ColumnarTraceGen, generate_traces
    from zipkin_tpu.wal import WriteAheadLog

    # run_replication's geometry: every ingest-step compile this phase
    # needs is already warm by the time it runs.
    config = dev.StoreConfig(
        capacity=1 << 10, ann_capacity=1 << 12, bann_capacity=1 << 11,
        max_services=32, max_span_names=128, max_annotation_values=256,
        max_binary_keys=64, cms_width=1 << 10, hll_p=8,
        quantile_buckets=512,
    )
    traces = generate_traces(n_traces=1000, max_depth=3, n_services=16)
    spans = [s for t in traces for s in t][:1280]
    chunk = 128
    root = tempfile.mkdtemp(prefix="fleet-obs-smoke-")
    server = None
    follower = None
    stores = []
    wals = []
    try:
        # -- (a) live ship pair: one causally-linked trace ------------
        reg = obs.Registry()
        primary = TpuSpanStore(config)
        stores.append(primary)
        wal = WriteAheadLog(os.path.join(root, "wal-pair"), fsync="off")
        wals.append(wal)
        primary.attach_wal(wal)
        tracker = fobs.LineageTracker(primary.apply, registry=reg,
                                      sample_every=1)
        primary.attach_lineage(tracker)
        shipper = WalShipper(primary, registry=reg, tracker=tracker)
        server = ShipServer(shipper, host="127.0.0.1", port=0)
        port = server.server_address[1]
        server.serve_in_thread()

        freg = obs.Registry()
        rc = ShipClient("127.0.0.1", port, "smoke-fleet-replica",
                        mode="replica")
        replica = ReplicaSpanStore(dev.config_from_dict(
            rc.connect()["config"]), background_compaction=False)
        stores.append(replica)
        flin = fobs.FollowerLineage("smoke-fleet-replica",
                                    mode="replica", registry=freg)
        follower = Follower(ReplicaTarget(replica), rc,
                            registry=freg, lineage=flin)
        for i in range(0, len(spans), chunk):
            primary.apply(spans[i:i + chunk])
        wal.sync()
        deadline = time.perf_counter() + 60.0
        while (replica.applied_seq() < wal.last_seq
               and time.perf_counter() < deadline):
            follower.step()
        follower.step()  # backhaul the buffered apply spans + metrics
        tracker.flush()
        wal.sync()

        want = {"ingest unit", "wal append", "wal fsync", "ship",
                "replica apply"}
        trace_roundtrip = False
        parent_ids_ok = False
        for itid in primary.get_trace_ids_by_name(
                "zipkin-tpu", None, 1 << 62, 64):
            trace = primary.get_spans_by_trace_ids([itid.trace_id])[0]
            names = {s.name for s in trace}
            if not (want <= names):
                continue
            trace_roundtrip = True
            roots = [s for s in trace
                     if s.name == "ingest unit" and s.parent_id is None]
            parent_ids_ok = bool(roots) and all(
                s.parent_id == roots[0].id
                and s.trace_id == roots[0].trace_id
                for s in trace if s.name in want - {"ingest unit"})
            break

        # -- (b) federation merge: bitwise vs own scrapes -------------
        fleet = fobs.FleetObs(
            role="primary", registry=reg, tracker=tracker,
            remote_sources=shipper.fleet_sources,
            replication=shipper.status)
        fed = fleet.federated_text()
        labels_ok = ('role="primary"' in fed
                     and 'follower="smoke-fleet-replica"' in fed)

        def _vals(text):
            out = []
            for line in text.splitlines():
                if line and not line.startswith("#"):
                    name = line.split("{")[0].split(" ")[0]
                    out.append((name, line.rsplit(" ", 1)[1]))
            return sorted(out)

        # The follower's snapshot was pushed over FETCH meta; its
        # samples in the merged view must format exactly as its own
        # scrape does (values may have advanced since the push, so
        # compare a fresh snapshot rendered through the fed path).
        snap = fobs.registry_snapshot(freg)
        fed_solo = fobs.render_federated([((), snap)])
        federation_bitwise = _vals(fed_solo) == _vals(freg.render_text())
        visible_lag_recorded = (
            "zipkin_replication_visible_lag_seconds" in fed
            and flin.lag_seconds() is not None)

        # -- (c) watchdog fires on an injected fsync stall ------------
        rec_ring = fobs.FlightRecorder()
        wd = fobs.Watchdog(recorder=rec_ring, registry=reg)
        wd.add_probe("wal_fsync", fobs.fsync_parked_probe(wal))
        ok_before = wd.check()["ready"]
        wal._sync_error = RuntimeError("injected fsync stall")
        fired = wd.check()
        wal._sync_error = None
        cleared = wd.check()
        watchdog_fired = (ok_before and not fired["ready"]
                          and "injected fsync stall"
                          in fired["reasons"][0]["reason"])
        watchdog_cleared = bool(cleared["ready"] and len(rec_ring) == 2)

        # -- (d) zero new device launches -----------------------------
        def drive(store):
            for i in range(0, len(spans), chunk):
                store.apply(spans[i:i + chunk])

        off = TpuSpanStore(config)  # no lineage: the census's other side
        stores.append(off)
        on = TpuSpanStore(config)
        stores.append(on)
        wal_on = WriteAheadLog(os.path.join(root, "wal-on"),
                               fsync="off")
        wals.append(wal_on)
        on.attach_wal(wal_on)
        trk_on = fobs.LineageTracker(on.apply, registry=obs.Registry())
        on.attach_lineage(trk_on)  # production cadence (1-in-64)
        drive(on)  # warm every pad bucket the gated drive will hit
        compiles0 = dev.compile_count() + dev.query_compile_count()
        drive(on)
        lineage_compiles = (dev.compile_count()
                            + dev.query_compile_count() - compiles0)

        def _census(store):
            db = dev.make_device_batch(
                *ColumnarTraceGen(store.dicts, n_services=8)
                .next_batch(8),
                pad_spans=512, pad_anns=1024, pad_banns=512)
            return _count_ops(
                dev.ingest_step.lower(store.state, db).as_text())

        census_on = _census(on)
        census_off = _census(off)

        return {
            "spans": len(spans),
            "trace_roundtrip": bool(trace_roundtrip),
            "parent_ids_ok": bool(parent_ids_ok),
            "federation_labels_ok": bool(labels_ok),
            "federation_bitwise": bool(federation_bitwise),
            "visible_lag_recorded": bool(visible_lag_recorded),
            "watchdog_fired": bool(watchdog_fired),
            "watchdog_cleared": bool(watchdog_cleared),
            "lineage_steady_state_compiles": int(lineage_compiles),
            "census_equal": census_on == census_off,
            "fleet_processes": len(fleet.status()["processes"]),
        }
    finally:
        if follower is not None:
            follower.close()
        if server is not None:
            server.shutdown()
            server.server_close()
        for s in stores:
            close = getattr(s, "close", None)
            if close is not None:
                try:
                    close()
                except Exception:  # noqa: BLE001 — teardown best-effort
                    pass
        for w in wals:
            try:
                w.close()
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass
        shutil.rmtree(root, ignore_errors=True)


def run_lint() -> dict:
    """graftlint phase (tier-1 gated): the concurrency/JAX-hazard
    analyzer (zipkin_tpu/analysis, docs/STATIC_ANALYSIS.md) over the
    whole package against the checked-in baseline. Zero NEW findings
    is the gate — the lock-order/guarded-by/sync-under-lock/jit
    conventions the write path depends on stay machine-checked on
    every CI run."""
    import os

    from zipkin_tpu.analysis import ALL_RULES, analyze, load_project
    from zipkin_tpu.analysis import baseline as lint_baseline

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    project = load_project([os.path.join(repo, "zipkin_tpu")], repo)
    findings = analyze(project)
    base_path = os.path.join(repo, "graftlint-baseline.json")
    if os.path.exists(base_path):
        new, stale = lint_baseline.diff(
            findings, lint_baseline.load(base_path))
    else:
        new, stale = findings, []
    return {
        "files": len(project.modules),
        "locks": len(project.locks),
        "rules": len(ALL_RULES),
        "findings_total": len(findings),
        "findings_new": len(new),
        "stale_baseline_entries": len(stale),
        "new": [f.render() for f in new[:20]],
    }


def run(total_spans: int = 7000, k_queries: int = 8) -> dict:
    import numpy as np  # noqa: F401  (kept: smoke envs import-check it)

    from zipkin_tpu.store import device as dev
    from zipkin_tpu.store.tpu import TpuSpanStore
    from zipkin_tpu.tracegen import ColumnarTraceGen

    config = dev.StoreConfig(
        capacity=1 << 12, ann_capacity=1 << 13, bann_capacity=1 << 12,
        max_services=64, max_span_names=128, max_annotation_values=512,
        max_binary_keys=128, cms_width=1 << 12, hll_p=8,
        quantile_buckets=512,
        # Pin the counting rank path: the op-count gate below is the
        # COUNTING path's census (census.LOWERING_TABLE). "auto" would pick
        # argsort on the CPU CI backend (backend-aware policy,
        # dev.rank_mode) and gate the wrong lowering.
        rank_path="counting",
    )
    store = TpuSpanStore(config)
    gen = ColumnarTraceGen(store.dicts, n_services=32, n_span_names=64,
                           spans_per_trace=7)
    batch_traces = 64
    pad_s, pad_a, pad_b = 512, 1024, 512
    dbs = []
    n_batches = max(1, total_spans // (batch_traces * 7))
    for _ in range(n_batches):
        batch, name_lc, indexable = gen.next_batch(batch_traces)
        dbs.append(dev.make_device_batch(
            batch, name_lc, indexable,
            pad_spans=pad_s, pad_anns=pad_a, pad_banns=pad_b,
        ))

    # Op-count census of the fused step's lowering (the compile below
    # shares the jit cache, so this adds a trace, not a compile). The
    # telemetry counter block must stay a pure read: its lowering may
    # contain NO scatter/sort, and the step census is taken with the
    # obs layer fully wired — together they prove the device counter
    # fetch adds zero passes (tests/test_bench_smoke.py gates both).
    state = store.state
    ops = _count_ops(dev.ingest_step.lower(state, dbs[0]).as_text())
    cb_ops = _count_ops(dev.counter_block.lower(state).as_text())

    # The main stream: every batch through the fused step. "spans" is
    # what the device counted past the first (compiling) step.
    state = dev.ingest_step(state, dbs[0])
    import jax

    warm = int(jax.device_get(state.counters["spans_seen"]))
    for db in dbs:
        state = dev.ingest_step(state, db)
    seen = int(jax.device_get(state.counters["spans_seen"]))
    total = seen - warm
    store.adopt_state(state, spans_written=seen)

    # Batched queries: k singular launches against one multi launch.
    end_ts = int(jax.device_get(state.ts_max)) + 1
    svcs = sorted(store.get_all_service_names())
    queries = [
        ("name", svcs[i % len(svcs)], None, end_ts, 10)
        for i in range(k_queries)
    ]

    def serial():
        return [store.get_trace_ids_by_name(q[1], q[2], q[3], q[4])
                for q in queries]

    def batched():
        return store.get_trace_ids_multi(queries)

    want = serial()
    got = batched()
    identical = [
        [(i.trace_id, i.timestamp) for i in ids] for ids in got
    ] == [
        [(i.trace_id, i.timestamp) for i in ids] for ids in want
    ]

    from zipkin_tpu.store import census

    return {
        "metric": "bench_smoke",
        "archive": run_archive(),
        "pipeline": run_pipeline(),
        "wal": run_wal(),
        "query": run_query(),
        "ingest_structure": run_ingest_structure(),
        "windows": run_windows(),
        "paged": run_paged(),
        "replication": run_replication(),
        "sharded": run_sharded(),
        "fleet_obs": run_fleet_obs(),
        "lint": run_lint(),
        # The main stream runs the library default (window arena OFF),
        # so its step census gates at the BASE ceilings; the windows
        # phase gates the window-on lowering at BASE + WINDOW_BUMP.
        "census_ceilings": {
            "scatter": census.BASE_STEP_SCATTERS,
            "sort": census.BASE_STEP_SORTS,
            "gather": census.BASE_STEP_GATHERS,
        },
        "spans": total,
        "step_scatters": ops["scatter"],
        "step_gathers": ops["gather"],
        "step_sorts": ops["sort"],
        "telemetry": {
            "counter_block": store.counter_block(),
            "counter_block_scatters": cb_ops["scatter"],
            "counter_block_sorts": cb_ops["sort"],
        },
        "multi_query": {
            "k": k_queries,
            "identical": identical,
        },
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--spans", type=int, default=7000)
    ap.add_argument("--k", type=int, default=8)
    args = ap.parse_args()
    print(json.dumps(run(args.spans, args.k)), flush=True)


if __name__ == "__main__":
    sys.path.insert(0, ".")
    main()
