"""BASELINE.md benchmark harness — all five configs, one JSON line.

Configs (BASELINE.md / BASELINE.json):
  #1 CPU reference path: tracegen spans -> SQL store (the anormdb role,
     store/sql.py) — ingest rate, index-query latency, and the
     incremental dependency-aggregation job (AnormAggregator.scala:32-90
     semantics). This is the honest ``vs_baseline`` denominator.
  #2 TPU ingest: stream N spans (default 100M+) of 1k-service tracegen
     traffic through the fused device ingest_step at ring capacity 2^22,
     with the production dependency-archive policy running in-loop.
  #3 dep-link queries: get_dependencies() p50/p99 off the streaming bank.
  #4 per-service latency percentiles (p50/p95/p99) off the device
     log-histogram, p50/p99 latency.
  #5 cardinality (HLL distinct traces) + top-k annotations, p50/p99.
  Plus the read path operators care about: get_trace_ids by service /
  span name / annotation / binary value, durations, and whole-trace
  materialization, each timed wall-clock through the public SpanStore
  API (device kernel + host decode — what an API call pays) — and the
  batched-query phase (bench_batched_queries): k queries through one
  get_trace_ids_multi launch vs k singular dispatches, the
  dispatch-floor amortization the API's query coalescer rides.

Span stream: one device-resident template batch, re-stamped ON DEVICE
each step (trace/span/parent ids XOR a per-step salt — preserving the
join structure — and timestamps shifted forward), so 100M *distinct*
spans stream at device rate without host generation in the loop.

Usage:
  python bench.py                  # full run (real TPU, ~100M spans)
  python bench.py --smoke          # small shapes (CI / CPU)
  python bench.py --compare-kernels  # + XLA vs pallas scatter ingest
  python bench.py --spans 2e8      # override stream length

Prints ONE json line: {"metric", "value", "unit", "vs_baseline",
"detail": {...}} — value is TPU ingest spans/sec, vs_baseline is
against the SQL CPU reference path (config #1).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Optional

import numpy as np


def _log(msg: str) -> None:
    print(f"[bench +{time.perf_counter() - _T0:8.1f}s] {msg}",
          file=sys.stderr, flush=True)


_T0 = time.perf_counter()

SPT = 7  # spans per generated trace


def _pctl(samples_ms):
    a = np.asarray(samples_ms, np.float64)
    return {
        "p50_ms": round(float(np.percentile(a, 50)), 3),
        "p99_ms": round(float(np.percentile(a, 99)), 3),
    }


def _timeit(fn, reps: int, warmup: int = 2):
    for _ in range(warmup):
        fn()
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        out.append((time.perf_counter() - t0) * 1e3)
    return _pctl(out)


# ---------------------------------------------------------------------------
# Config #1 — CPU reference path (SQL store, the anormdb role)
# ---------------------------------------------------------------------------


def bench_sql_baseline(total_spans: int = 10_000):
    from zipkin_tpu.aggregate.job import IncrementalAggregator
    from zipkin_tpu.store.sql import SqliteSpanStore
    from zipkin_tpu.tracegen import generate_traces

    traces = generate_traces(
        n_traces=max(1, total_spans // 8), max_depth=5, n_services=10
    )
    spans = [s for t in traces for s in t][:total_spans]
    store = SqliteSpanStore()
    t0 = time.perf_counter()
    for i in range(0, len(spans), 500):
        store.apply(spans[i:i + 500])
    ingest_s = time.perf_counter() - t0
    svc = sorted(store.get_all_service_names())[0]
    end_ts = max(s.last_timestamp for s in spans if s.last_timestamp) + 1
    q_ids = _timeit(
        lambda: store.get_trace_ids_by_name(svc, None, end_ts, 10), reps=20
    )
    q_ann = _timeit(
        lambda: store.get_trace_ids_by_annotation(svc, "custom", None,
                                                  end_ts, 10),
        reps=10, warmup=1,
    )
    agg = IncrementalAggregator()
    t0 = time.perf_counter()
    agg.offer(spans)
    dep_job_s = time.perf_counter() - t0
    store.close()
    return {
        "spans": len(spans),
        "ingest_spans_per_s": round(len(spans) / ingest_s, 1),
        "q_trace_ids_by_service": q_ids,
        "q_trace_ids_by_annotation": q_ann,
        "dep_job_spans_per_s": round(len(spans) / dep_job_s, 1),
        "dep_links": len(agg.result().links),
    }


# ---------------------------------------------------------------------------
# Configs #2-#5 — the TPU store at scale
# ---------------------------------------------------------------------------


def _tpu_config(capacity_log2: int, n_services: int, use_pallas: bool,
                rank_path: str = "auto"):
    from zipkin_tpu.store import device as dev

    # Index sizing for the benchmark's UNIFORM key space (1k services x
    # 2k span names => ~2M live (host, name) pairs; the default derived
    # geometry caps far below that):
    # - (service, span-name) family slots ~2x the annotation ring, so in
    #   steady state everything a bucket displaced is already evicted
    #   and the per-key displaced-gid gate holds (the tr_wm sizing rule,
    #   store/device.py) — by-name queries answer from the index instead
    #   of the O(ring) scan;
    # - per-key cursor table ~2x the live key count, so claims don't
    #   saturate and sparse pairs keep their records.
    # Cost at capacity 2^22: ~+330MB name family, ~+66MB key table.
    big = capacity_log2 >= 20
    return dev.StoreConfig(
        capacity=1 << capacity_log2,
        ann_capacity=1 << (capacity_log2 + 1),
        bann_capacity=1 << capacity_log2,
        max_services=n_services,
        max_span_names=2048,
        max_annotation_values=4096,
        max_binary_keys=1024,
        cms_width=1 << 16,
        hll_p=14,
        quantile_buckets=2048,
        use_pallas=use_pallas,
        rank_path=rank_path,
        idx_name_buckets=(1 << 16) if big else 0,
        idx_name_depth=256 if big else 0,
        # ~4x the live key count: the i32-fingerprint claims (probes=3)
        # fail ~load^3, so load 0.25 keeps ~98%+ of keys recorded and
        # by-name queries on the fast path. i32 fps made slots half
        # price (~34MB table + ~67MB watermarks at 2^23).
        idx_key_slots=(1 << 23) if big else 0,
        # One dependency bucket closes per half ring (~2M spans): 64
        # time-tagged banks keep ~128M spans of windowed dependency
        # resolution before older windows fold into the all-time tail
        # (the hourly-Dependencies-rows fidelity at stream scale;
        # +1.0GB at S=1024, within the 16GB budget).
        dep_buckets=64 if big else 16,
    )


def _make_template(store, n_services: int, batch_traces: int):
    """One device-resident template batch + the jitted per-step restamp."""
    import jax
    import jax.numpy as jnp

    from zipkin_tpu.store import device as dev
    from zipkin_tpu.tracegen import ColumnarTraceGen

    from functools import partial

    gen = ColumnarTraceGen(
        store.dicts, n_services=n_services, n_span_names=2048,
        spans_per_trace=SPT, topology=True,
    )
    batch, name_lc, indexable = gen.next_batch(batch_traces)
    pad_spans = batch_traces * SPT
    db0 = dev.make_device_batch(
        batch, name_lc, indexable,
        pad_spans=pad_spans, pad_anns=2 * pad_spans, pad_banns=pad_spans,
    )
    db0 = jax.device_put(db0)

    def restamp(db, step):
        """Restamp the template ON DEVICE (salt/delta derived from a
        device-carried step counter — a host scalar per step would pay a
        host round trip each). XOR keeps span_id = trace_id ^ node and
        the parent join structure intact; time advances one minute per
        batch.

        The salt is splitmix64(step): a multiplicative salt correlates
        with the golden-multiplied template trace ids and produces
        structured cross-batch id collisions (~1 in 700 rows, measured),
        which fabricate cross-trace parent joins in the benchmark data.
        """
        s = (step + 1).astype(jnp.uint64)
        s = (s ^ (s >> 30)) * jnp.uint64(0xBF58476D1CE4E5B9)
        s = (s ^ (s >> 27)) * jnp.uint64(0x94D049BB133111EB)
        salt = (s ^ (s >> 31)).astype(jnp.int64)
        delta = step * jnp.int64(60_000_000)

        def shift(ts):
            return jnp.where(ts >= 0, ts + delta, ts)

        return db._replace(
            trace_id=db.trace_id ^ salt,
            span_id=db.span_id ^ salt,
            parent_id=jnp.where(db.has_parent, db.parent_id ^ salt,
                                jnp.int64(0)),
            ts_cs=shift(db.ts_cs), ts_cr=shift(db.ts_cr),
            ts_sr=shift(db.ts_sr), ts_ss=shift(db.ts_ss),
            ts_first=shift(db.ts_first), ts_last=shift(db.ts_last),
            ann_ts=shift(db.ann_ts),
        )

    @partial(jax.jit, donate_argnums=(0, 2), static_argnums=(3,))
    def fused_chain(state, db, step, k, do_close):
        """k restamp+ingest steps per LAUNCH via lax.scan: one dispatch
        amortizes over k batches instead of being paid per batch (round
        3 measured ~100ms per dispatch vs ~5-7ms per scan iteration;
        not re-measured). ``do_close`` folds the
        dependency-bucket close (the archive-cadence launch) into the
        same dispatch: lax.cond executes one branch at runtime, so a
        False close is near-free and a True one saves a whole call
        floor."""
        state = jax.lax.cond(
            do_close, dev.dep_close_bucket.__wrapped__, lambda s: s,
            state,
        )

        def body(carry, _):
            st, stp = carry
            st = dev.ingest_step.__wrapped__(st, restamp(db, stp))
            return (st, stp + 1), None

        (state, step), _ = jax.lax.scan(
            body, (state, step), None, length=k
        )
        return state, step

    return db0, fused_chain, pad_spans


def _hlo_stats(jitfn, *args):
    """Instruction/fusion/sort counts of the compiled module's entry
    computation — the op-count evidence round 3 tracked by hand.
    Uses the AOT lowering path, which shares the jit compile cache, so
    this costs one (cached) compile, not two."""
    try:
        txt = jitfn.lower(*args).compile().as_text()
        entry, depth, counts = False, 0, {"instr": 0, "fusion": 0,
                                          "sort": 0}
        for line in txt.splitlines():
            s = line.strip()
            if s.startswith("ENTRY "):
                entry, depth = True, 0
            if not entry:
                continue
            depth += s.count("{") - s.count("}")
            if " = " in s:
                counts["instr"] += 1
                if " fusion(" in s:
                    counts["fusion"] += 1
                if " sort(" in s:
                    counts["sort"] += 1
            if depth <= 0 and "}" in s and counts["instr"]:
                break
        return (f"{counts['instr']} entry instrs, "
                f"{counts['fusion']} fusions, {counts['sort']} sorts")
    except Exception as e:  # noqa: BLE001 — diagnostics only
        return f"hlo stats unavailable: {e!r}"


def _telemetry_block(store) -> dict:
    """Per-stage telemetry for the BENCH json: the store's device
    counter block plus every non-empty latency sketch registered in the
    process registry (stage p50/p99 summaries)."""
    from zipkin_tpu import obs

    out = {}
    cb = getattr(store, "counter_block", None)
    if callable(cb):
        try:
            out["counter_block"] = cb()
        except Exception as e:  # telemetry must never sink a bench
            out["counter_block_error"] = str(e)
    sketches = {}
    for m in obs.default_registry().collect():
        if isinstance(m, obs.LatencySketch):
            items = ([(m.name, m)] if not m.labelnames else [
                (f"{m.name}{dict(labels)}", child)
                for labels, child in m._child_items()
            ])
            for name, sk in items:
                if sk.count:
                    sketches[name] = sk.snapshot()
    if sketches:
        out["sketches"] = sketches
    return out


def bench_tpu_stream(total_spans: int, capacity_log2: int = 22,
                     n_services: int = 1024, batch_traces: int = 16384,
                     use_pallas: bool = False, rank_path: str = "auto"):
    """Stream ``total_spans`` through the fused ingest (config #2) and
    return (store-with-final-state, ingest stats)."""
    import jax
    import jax.numpy as jnp

    from zipkin_tpu.store import device as dev
    from zipkin_tpu.store.tpu import TpuSpanStore

    config = _tpu_config(capacity_log2, n_services, use_pallas,
                         rank_path)
    store = TpuSpanStore(config)
    cap = config.capacity
    # One launch must never outrun the archive cadence (one dependency-
    # bucket close per half ring) nor wrap the ring within itself: the
    # whole stream loop is built on spans_per_call <= cap/2. Clamp
    # oversized --batch-traces instead of silently corrupting state.
    max_traces = max(1, (cap // 2) // SPT)
    if batch_traces > max_traces:
        _log(f"stream: --batch-traces {batch_traces} exceeds half-ring "
             f"budget; clamped to {max_traces}")
        batch_traces = max_traces
    db0, fused_chain, pad_spans = _make_template(
        store, n_services, batch_traces
    )
    # Chain length: as many batches per launch as fit HALF the ring
    # (the archive cadence closes a dependency bucket once per half
    # capacity, and a single launch must not outrun it), capped at 32.
    chain = max(1, min(32, (cap // 2) // pad_spans))
    spans_per_call = chain * pad_spans

    def sync(x):
        # A real barrier: device_get forces the D2H round trip, so the
        # stream is never credited with dispatch time only.
        return float(jax.device_get(x))

    # Warm the compile caches on a throwaway state (donated away) and
    # record the compiled step's HLO shape (op-count discipline:
    # per-kernel overhead prices every extra instruction).
    _log(f"stream: compiling (capacity 2^{capacity_log2}, "
         f"{n_services} services, chain {chain}, pallas={use_pallas})")
    wstate = dev.init_state(config)
    hlo = _hlo_stats(fused_chain, wstate, db0, jnp.int64(0), chain,
                     jnp.bool_(False))
    wstate, wstep = fused_chain(wstate, db0, jnp.int64(0), chain,
                                jnp.bool_(True))
    sync(wstate.counters["spans_seen"])
    _log(f"stream: ingest (+fused bucket close) compiled ({hlo})")
    del wstate, wstep

    state = store.state
    step = jnp.int64(0)
    wp = archived = 0
    n_calls = max(1, total_spans // spans_per_call)
    archive_runs = 0
    t0 = time.perf_counter()
    for i in range(n_calls):
        # Production archive policy (TpuSpanStore._maybe_archive), at
        # launch granularity: one chained launch ingests spans_per_call
        # spans (<= cap/2 by construction). The bucket close rides the
        # SAME launch via the fused do_close flag.
        do_close = wp + spans_per_call - archived > cap
        if do_close:
            archived = min(
                wp, max(wp + spans_per_call - cap, wp - cap // 2)
            )
            archive_runs += 1
        state, step = fused_chain(state, db0, step, chain,
                                  jnp.bool_(do_close))
        wp += spans_per_call
        if (i + 1) % 8 == 0:
            # True barrier every 8 launches: bounds the async queue
            # depth and keeps the measured rate honest.
            sync(state.counters["spans_seen"])
    seen = sync(state.counters["spans_seen"])
    dt = time.perf_counter() - t0
    total = n_calls * spans_per_call
    assert seen == total, (seen, total)
    _log(f"stream: {total} spans in {dt:.1f}s "
         f"({total / dt / 1e6:.1f}M spans/s, "
         f"{archive_runs} archive passes, chain {chain})")

    # Hand the streamed state to the store so the public query API
    # (device kernels + host decode) serves the read benchmarks.
    store.adopt_state(state, spans_written=wp, archived=archived)
    stats = {
        "spans": total,
        "spans_per_s": round(total / dt, 1),
        "wall_s": round(dt, 2),
        "ring_capacity": cap,
        "services": n_services,
        "batch_spans": pad_spans,
        "chain": chain,
        "archive_runs": archive_runs,
        "use_pallas": use_pallas,
        # Active kernel paths (r12): which rank / arena-scatter
        # implementations the compiled steps took — "auto"/"counting"
        # degrade statically (wm_shift == 0, scratch budget, VMEM
        # fit), so the record must say what actually ran.
        "rank_path": dev.active_paths(config).get("rank", ()),
        "scatter_path": dev.active_paths(config).get("scatter", ()),
        "ring_write_path": dev.active_paths(config).get("ring_write", ()),
        # Per-stage telemetry: the device counter block (one fused
        # fetch — ring occupancy/laps, poison census, ingest counters)
        # rides the BENCH json so remote runs surface the same
        # observables /metrics serves live (docs/OBSERVABILITY.md).
        "telemetry": _telemetry_block(store),
    }
    return store, stats


def bench_tpu_queries(store, reps: int = 12):
    """Configs #3-#5 + the get_trace_ids read path, through the public
    SpanStore API (wall-clock: device kernel + host materialization)."""
    _log("queries: starting")
    state = store.state
    end_ts = int(state.ts_max) + 1
    S = store.config.max_services
    rng = np.random.default_rng(7)
    svcs = [f"svc-{i:04d}" for i in rng.integers(0, S, size=reps * 2)]
    it = iter(range(10**9))

    def next_svc():
        return svcs[next(it) % len(svcs)]

    out = {}
    out["q_trace_ids_by_service"] = _timeit(
        lambda: store.get_trace_ids_by_name(next_svc(), None, end_ts, 10),
        reps=reps,
    )
    out["q_trace_ids_by_span_name"] = _timeit(
        lambda: store.get_trace_ids_by_name(
            next_svc(), f"op-{next(it) % 2048:04d}", end_ts, 10
        ),
        reps=reps,
    )
    out["q_trace_ids_by_annotation"] = _timeit(
        lambda: store.get_trace_ids_by_annotation(
            next_svc(), "some custom annotation", None, end_ts, 10
        ),
        reps=max(5, reps // 2),
    )
    out["q_trace_ids_by_binary_value"] = _timeit(
        lambda: store.get_trace_ids_by_annotation(
            next_svc(), "http.uri", b"/api/widgets", end_ts, 10
        ),
        reps=max(5, reps // 2),
    )

    # Trace materialization + durations on ids a query actually returned.
    seed_ids = []
    for _ in range(20):
        seed_ids.extend(
            i.trace_id
            for i in store.get_trace_ids_by_name(next_svc(), None, end_ts, 10)
        )
        if len(seed_ids) >= 100:
            break
    seed_ids = seed_ids[:100] or [1]
    out["q_get_trace"] = _timeit(
        lambda: store.get_spans_by_trace_ids(
            [seed_ids[next(it) % len(seed_ids)]]
        ),
        reps=reps,
    )
    out["q_durations_100"] = _timeit(
        lambda: store.get_traces_duration(seed_ids), reps=max(5, reps // 2)
    )

    # Config #3: dependency links off the streaming bank.
    deps = store.get_dependencies()
    out["dep_links"] = len(deps.links)
    out["q_dependencies"] = _timeit(
        lambda: store.get_dependencies(), reps=max(5, reps // 2)
    )
    # Config #4: per-service latency percentiles.
    out["q_quantiles"] = _timeit(
        lambda: store.service_duration_quantiles(next_svc(), [0.5, 0.95, 0.99]),
        reps=reps,
    )
    # Config #5: top-k + cardinality.
    out["q_top_annotations"] = _timeit(
        lambda: store.top_annotations(next_svc(), 10), reps=reps
    )
    out["q_hll_cardinality"] = _timeit(
        lambda: store.estimated_unique_traces(), reps=reps
    )
    out["est_unique_traces"] = round(store.estimated_unique_traces(), 1)
    out["q_service_names"] = _timeit(
        lambda: store.get_all_service_names(), reps=max(5, reps // 2)
    )
    worst = max(
        v["p99_ms"] for k, v in out.items()
        if isinstance(v, dict) and "p99_ms" in v
    )
    out["worst_query_p99_ms"] = worst
    _log(f"queries: done (worst p99 {worst:.0f}ms)")
    return out


def bench_batched_queries(store, ks=(1, 4, 16, 64), reps: int = 5):
    """The query dispatch-floor amortization (r6 read-side tentpole):
    k concurrent API queries ride ONE ``get_trace_ids_multi`` launch
    (the tier QueryService's cross-request coalescer feeds) instead of
    k ~100 ms dispatches. Per k: wall-clock of k serial singular calls
    vs one batched call, identity of the results, and the implied
    aggregate queries/s — the scaling-with-batch-size evidence the
    acceptance gate asks for (batched < 0.5 x serial at k >= 4 on
    dispatch-floor-dominated hardware)."""
    _log("batched-queries: starting")
    state = store.state
    end_ts = int(state.ts_max) + 1
    S = store.config.max_services
    rng = np.random.default_rng(23)
    out = {}
    for k in ks:
        svcs = [f"svc-{i:04d}" for i in rng.integers(0, S, size=k)]
        queries = [("name", s, None, end_ts, 10) for s in svcs]

        def serial():
            return [store.get_trace_ids_by_name(s, None, end_ts, 10)
                    for s in svcs]

        def batched():
            return store.get_trace_ids_multi(queries)

        t_serial = _timeit(serial, reps=reps, warmup=1)
        t_batched = _timeit(batched, reps=reps, warmup=1)
        identical = [
            [(i.trace_id, i.timestamp) for i in ids] for ids in serial()
        ] == [
            [(i.trace_id, i.timestamp) for i in ids] for ids in batched()
        ]
        ratio = (t_batched["p50_ms"] / t_serial["p50_ms"]
                 if t_serial["p50_ms"] else 0.0)
        out[f"k{k}"] = {
            "serial": t_serial, "batched": t_batched,
            "batched_over_serial_p50": round(ratio, 3),
            "batched_queries_per_s": round(
                k / (t_batched["p50_ms"] / 1e3), 1
            ) if t_batched["p50_ms"] else 0.0,
            "identical": identical,
        }
        _log(f"batched-queries: k={k} serial p50 "
             f"{t_serial['p50_ms']:.1f}ms batched p50 "
             f"{t_batched['p50_ms']:.1f}ms identical={identical}")
    return out


def bench_query_engine(store, reps: int = 20, concurrency: int = 8):
    """Resident query engine (r11 tentpole, query/engine.py): the
    ~105-115 ms per-request dispatch floor every query family paid at
    1B spans (BENCH_1B.json), attacked on three tiers. Measures, on
    the live streamed store:

    - sketch tier: quantiles / top-k / HLL / catalogs off the host
      mirror — target p50 < 10 ms (acceptance gate; they are numpy
      reads, so this also proves the mirror resync path after the
      bench's adopt_state);
    - index tier: trace-id reads through the standing executor under
      ``concurrency`` concurrent callers — target p99 < 50 ms (one
      launch + one D2H shared per micro-batch vs one per request);
    - cache tier: repeat-read latency + bitwise hit==cold identity;
    - zero steady-state recompiles across all of it (the resident
      programs stay resident).

    Sketch answers are cross-checked against the device read path on
    every rep (0 mismatches required, like the memory-oracle gates)."""
    import threading

    from zipkin_tpu.query.engine import QueryEngine

    _log("query-engine: starting")
    engine = QueryEngine(store, registry=_obs().Registry())
    state = store.state
    end_ts = int(state.ts_max) + 1
    S = store.config.max_services
    rng = np.random.default_rng(11)
    svcs = [f"svc-{i:04d}" for i in rng.integers(0, S, size=64)]
    it = iter(range(10**9))

    def next_svc():
        return svcs[next(it) % len(svcs)]

    engine.get_all_service_names()  # resync the mirror (one fetch)

    # Cross-check first, UNTIMED: the device read path costs the very
    # dispatch floor the sketch tier avoids, so it must never sit
    # inside the measured round (the p50 < 10ms gate would otherwise
    # be structurally unreachable on a device store).
    mismatches = 0
    for _ in range(reps):
        s = next_svc()
        if (engine.service_duration_quantiles(s, [0.5, 0.95, 0.99])
                != store.service_duration_quantiles(s, [0.5, 0.95,
                                                        0.99])):
            mismatches += 1
        if engine.top_annotations(s) != store.top_annotations(s):
            mismatches += 1
        if (engine.estimated_unique_traces()
                != store.estimated_unique_traces()):
            mismatches += 1

    def sketch_round():
        s = next_svc()
        engine.service_duration_quantiles(s, [0.5, 0.95, 0.99])
        engine.top_annotations(s)
        engine.estimated_unique_traces()
        engine.get_all_service_names()

    out = {"sketch": _timeit(sketch_round, reps=reps)}
    out["sketch"]["p50_ms"] = round(out["sketch"]["p50_ms"] / 4, 3)
    out["sketch"]["p99_ms"] = round(out["sketch"]["p99_ms"] / 4, 3)

    # Warm the multi-probe jit rows for every batch size the
    # concurrent drive can produce (1..concurrency requests per
    # micro-batch) plus the cache phase's fixed 8-query batch (its
    # pad-8 shape is otherwise unwarmed when --smoke drops
    # concurrency below 8): the p99 must measure dispatch, not
    # compiles — compiles are gated separately at zero AFTER this.
    for n in sorted(set(range(1, concurrency + 1)) | {8}):
        engine.executor.run(
            [("name", next_svc(), None, end_ts, 10)] * n)
    compiles0 = dev_compile_count()  # ingest + resident query jits

    # Index tier under concurrency: every caller's per-request latency
    # while `concurrency` threads hammer the standing executor.
    lat_ms: list = []
    lock = threading.Lock()

    def caller(n):
        mine = []
        for _ in range(reps):
            q = [("name", next_svc(), None, end_ts, 10)]
            t0 = time.perf_counter()
            engine.executor.run(q)  # cache-bypassing resident path
            mine.append((time.perf_counter() - t0) * 1e3)
        with lock:
            lat_ms.extend(mine)

    threads = [threading.Thread(target=caller, args=(i,))
               for i in range(concurrency)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    out["index_concurrent"] = {**_pctl(lat_ms),
                               "concurrency": concurrency}
    ex = engine.executor
    out["index_concurrent"]["launches_saved"] = ex.launches_saved
    out["index_concurrent"]["max_batch"] = ex.max_batch

    # Cache tier: cold vs hit, bitwise identity.
    queries = [("name", f"svc-{i:04d}", None, end_ts, 10)
               for i in range(8)]

    def ids(rows):
        return [[(i.trace_id, i.timestamp) for i in r] for r in rows]

    cold = ids(engine.get_trace_ids_multi(queries))
    out["cache_hit"] = _timeit(
        lambda: engine.get_trace_ids_multi(queries), reps=reps)
    hit_identical = ids(engine.get_trace_ids_multi(queries)) == cold
    out["sketch_mismatches"] = mismatches
    out["cache_hit_identical"] = bool(hit_identical)
    out["steady_recompiles"] = dev_compile_count() - compiles0
    out["meets_sketch_p50_target"] = out["sketch"]["p50_ms"] < 10.0
    out["meets_index_p99_target"] = (
        out["index_concurrent"]["p99_ms"] < 50.0)
    _log(f"query-engine: sketch p50 {out['sketch']['p50_ms']:.2f}ms "
         f"index-concurrent p99 "
         f"{out['index_concurrent']['p99_ms']:.1f}ms "
         f"cache-hit p50 {out['cache_hit']['p50_ms']:.2f}ms "
         f"recompiles {out['steady_recompiles']} "
         f"mismatches {mismatches}")
    return out


def _obs():
    from zipkin_tpu import obs

    return obs


def dev_compile_count() -> int:
    from zipkin_tpu.store import device as dev

    return dev.compile_count() + dev.query_compile_count()


def bench_exactness(store, n_queries: int = 24,
                    budget_s: float | None = None):
    """On-device index-vs-scan exactness: the same
    live store answers each sampled query through the index fast path
    AND with force_scan pinned; results must match id-for-id whenever
    the index claimed trust (when it degraded, both paths ran the same
    scan — trivially equal, still asserted).

    ``budget_s`` bounds the phase wall-clock: each force_scan replay is
    O(ring) (~15 s/check at the 100M config), and round 4 spent 771 s
    here — 13 minutes of the driver window re-proving what the suite
    proves structurally. Checks are interleaved across the query types
    (the durations/get_spans trace-membership pair runs FIRST — it is
    the only coverage those paths get), so an exhausted budget still
    leaves every path checked."""
    t_start = time.perf_counter()
    state = store.state
    end_ts = int(state.ts_max) + 1
    S = store.config.max_services
    rng = np.random.default_rng(11)
    svcs = [f"svc-{i:04d}" for i in rng.integers(0, S, size=n_queries)]
    checked = mismatches = 0
    detail = []
    budget_hit = False

    def over_budget():
        nonlocal budget_hit
        if budget_s is not None and (
                time.perf_counter() - t_start > budget_s):
            budget_hit = True
        return budget_hit

    def cmp(tag, fast, slow):
        nonlocal checked, mismatches
        checked += 1
        f = [(i.trace_id, i.timestamp) for i in fast]
        s = [(i.trace_id, i.timestamp) for i in slow]
        if f != s:
            mismatches += 1
            detail.append({"query": tag, "index": f[:5], "scan": s[:5]})

    # Trace membership first: durations through gid buckets vs full
    # scan — these two checks are the only exactness coverage the
    # trace-family paths get, so they must land inside any budget.
    ids = store.get_trace_ids_by_name(svcs[0], None, end_ts, 10)
    tids = [i.trace_id for i in ids][:10]
    if tids:
        checked += 1
        if (store.get_traces_duration(tids)
                != store.get_traces_duration(tids, force_scan=True)):
            mismatches += 1
            detail.append({"query": "durations"})
        checked += 1
        f = store.get_spans_by_trace_ids(tids)
        s = store.get_spans_by_trace_ids(tids, force_scan=True)
        if f != s:
            mismatches += 1
            detail.append({"query": "get_spans"})
    for i, svc in enumerate(svcs):
        if over_budget():
            break
        cmp(f"service:{svc}",
            store.get_trace_ids_by_name(svc, None, end_ts, 10),
            store.get_trace_ids_by_name(svc, None, end_ts, 10,
                                        force_scan=True))
        if over_budget():
            break
        if i % 3 == 0:
            name = f"op-{i % 2048:04d}"
            cmp(f"name:{svc}/{name}",
                store.get_trace_ids_by_name(svc, name, end_ts, 10),
                store.get_trace_ids_by_name(svc, name, end_ts, 10,
                                            force_scan=True))
        if i % 3 == 1:
            cmp(f"ann:{svc}",
                store.get_trace_ids_by_annotation(
                    svc, "some custom annotation", None, end_ts, 10),
                store.get_trace_ids_by_annotation(
                    svc, "some custom annotation", None, end_ts, 10,
                    force_scan=True))
        if i % 3 == 2:
            cmp(f"bann:{svc}",
                store.get_trace_ids_by_annotation(
                    svc, "http.uri", b"/api/widgets", end_ts, 10),
                store.get_trace_ids_by_annotation(
                    svc, "http.uri", b"/api/widgets", end_ts, 10,
                    force_scan=True))
    out = {"checked": checked, "mismatches": mismatches,
           "index_hits": store.index_hits,
           "scan_fallbacks": store.index_fallbacks,
           "wall_s": round(time.perf_counter() - t_start, 1)}
    if budget_hit:
        out["budget_exhausted_s"] = budget_s
    if detail:
        out["mismatch_detail"] = detail[:4]
    _log(f"exactness: {checked} checks, {mismatches} mismatches, "
         f"{store.index_hits} index hits / "
         f"{store.index_fallbacks} fallbacks"
         + (f" (budget {budget_s:.0f}s exhausted)" if budget_hit else ""))
    return out


def _bounded(fn, timeout_s: float, label: str):
    """Run ``fn`` on a daemon thread with a deadline. On timeout the
    thread is abandoned (a blocked device transfer is uninterruptible
    from Python) and a timeout record returned; callers must schedule
    bounded work LAST so an abandoned device operation can't block
    later device work."""
    import threading

    result = {}

    def run():
        try:
            result["value"] = fn()
        except Exception as e:  # noqa: BLE001
            result["error"] = repr(e)

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(timeout_s)
    if t.is_alive():
        _log(f"{label}: still running after {timeout_s:.0f}s — "
             "abandoned")
        return {"timed_out_s": timeout_s}
    if "error" in result:
        return {"error": result["error"]}
    return result.get("value")


def bench_archive(total_spans: int = 100_000):
    """Cold-tier phase: stream ~4 ring turns through a TieredSpanStore
    (store/archive) and measure what the paging layer costs and buys —
    capture overhead vs an identical sink-less store (same spans, warm
    jit cache), cold trace-fetch latency over EVICTED traces, segment
    compression ratio, and identity vs the memory oracle on a sample.
    The ring is sized to total_spans/4 so the stream laps it ~4x."""
    import numpy as np  # noqa: F401

    from zipkin_tpu.store import device as dev
    from zipkin_tpu.store.archive import ArchiveParams, TieredSpanStore
    from zipkin_tpu.store.memory import InMemorySpanStore
    from zipkin_tpu.store.tpu import TpuSpanStore
    from zipkin_tpu.tracegen import generate_traces

    cap = 1 << max(9, (total_spans // 4).bit_length() - 1)
    config = dev.StoreConfig(
        capacity=cap, ann_capacity=4 * cap, bann_capacity=2 * cap,
        max_services=64, max_span_names=256,
        max_annotation_values=512, max_binary_keys=64,
        cms_width=1 << 12, hll_p=10, quantile_buckets=512,
    )
    _log(f"archive phase: ring 2^{cap.bit_length() - 1}, "
         f"{total_spans} spans (~4 laps)")
    spans = []
    while len(spans) < total_spans:
        spans.extend(
            s for t in generate_traces(
                n_traces=max(total_spans // 5, 64), max_depth=3,
                n_services=32,
            ) for s in t
        )
    spans = spans[:total_spans]
    chunk = 1024

    def stream(store):
        t0 = time.perf_counter()
        for i in range(0, len(spans), chunk):
            store.apply(spans[i:i + chunk])
        return time.perf_counter() - t0

    stream(TpuSpanStore(config))  # jit warm-up (uncounted)
    plain_s = stream(TpuSpanStore(config))
    hot = TpuSpanStore(config)
    tiered = TieredSpanStore(
        hot, params=ArchiveParams.for_config(config))
    tiered_s = stream(tiered)

    oracle = InMemorySpanStore()
    oracle.apply(spans)
    tids = sorted({s.trace_id for s in spans})
    sample = tids[:3] + tids[len(tids) // 2:len(tids) // 2 + 3] \
        + tids[-3:]
    t0 = time.perf_counter()
    identical = all(
        tiered.get_spans_by_trace_ids([t])
        == oracle.get_spans_by_trace_ids([t]) for t in sample
    )
    cold_fetch_s = time.perf_counter() - t0
    c = tiered.counters()
    return {
        "spans": len(spans),
        "ring_capacity": cap,
        "ingest_plain_s": round(plain_s, 2),
        "ingest_tiered_s": round(tiered_s, 2),
        "capture_overhead_pct": round(
            100.0 * (tiered_s - plain_s) / plain_s, 1),
        "cold_fetch_ms_per_trace": round(
            cold_fetch_s / len(sample) * 1e3, 2),
        "segments_written": int(c["archive_segments_written"]),
        "compactions": int(c["archive_compactions"]),
        "segments_live": int(c["archive_segments_live"]),
        "cold_spans": int(c["archive_cold_spans"]),
        "cold_mb": round(c["archive_cold_bytes"] / 1e6, 2),
        "cold_compression_ratio": round(
            c["archive_cold_raw_bytes"]
            / max(c["archive_cold_bytes"], 1.0), 2),
        "capture_latency": tiered.archive.h_capture.snapshot(),
        "cold_query_latency": tiered.archive.h_cold_query.snapshot(),
        "identical_vs_oracle": bool(identical),
    }


def bench_pipeline(total_spans: int = 100_000, depth: int = 8,
                   capture_backlog: int = 64):
    """Pipelined-ingest phase (r9 tentpole): the same span stream
    driven through the serial write path (inline capture sealing) and
    through the three-stage pipeline (encode ∥ H2D staging ∥ device
    compute, async eviction sealer). On real hardware the interesting
    numbers are the spans/s delta (how much host encode + staging +
    capture sealing the pipeline hides behind device compute) and the
    overlap efficiency (stage-busy seconds / wall, > 1 means true
    overlap); equality of the device counter blocks plus a sample
    query double-checks identity cheaply (the bitwise-leaf proof runs
    on the CPU mesh every CI run — tests/test_pipeline.py)."""
    import numpy as np  # noqa: F401

    import jax

    from zipkin_tpu.store import device as dev
    from zipkin_tpu.store.archive import ArchiveParams, TieredSpanStore
    from zipkin_tpu.store.tpu import TpuSpanStore
    from zipkin_tpu.tracegen import generate_traces

    cap = 1 << max(9, (total_spans // 4).bit_length() - 1)
    config = dev.StoreConfig(
        capacity=cap, ann_capacity=4 * cap, bann_capacity=2 * cap,
        max_services=64, max_span_names=256,
        max_annotation_values=512, max_binary_keys=64,
        cms_width=1 << 12, hll_p=10, quantile_buckets=512,
    )
    _log(f"pipeline phase: ring 2^{cap.bit_length() - 1}, "
         f"{total_spans} spans, depth {depth}")
    spans = []
    while len(spans) < total_spans:
        spans.extend(
            s for t in generate_traces(
                n_traces=max(total_spans // 5, 64), max_depth=3,
                n_services=32,
            ) for s in t
        )
    spans = spans[:total_spans]
    chunk = 1024

    def build(backlog):
        hot = TpuSpanStore(config)
        hot.capture_backlog = backlog
        return hot, TieredSpanStore(
            hot, params=ArchiveParams.for_config(config))

    def stream(store):
        t0 = time.perf_counter()
        for i in range(0, len(spans), chunk):
            store.apply(spans[i:i + chunk])
        drain = getattr(store, "drain_pipeline", None)
        if drain is not None:
            drain()
            store.seal_barrier()
        return time.perf_counter() - t0

    # Warm BOTH modes' jit cache rows (staged device args key their
    # own entries — dev.stage_batch).
    _, warm_t = build(0)
    stream(warm_t)
    wh, wt = build(capture_backlog)
    wh.start_pipeline(depth)
    stream(wt)
    wt.close()

    sh, st = build(0)
    serial_s = stream(st)
    ph, pt = build(capture_backlog)
    compiles0 = dev.compile_count()
    pipe = ph.start_pipeline(depth)
    pipelined_s = stream(pt)
    recompiles = dev.compile_count() - compiles0
    encode_s, stage_s, commit_s = (
        pipe.h_encode.sum, pipe.h_stage.sum, pipe.h_commit.sum)
    stall_s = float(pipe.c_stall.value)
    ph.stop_pipeline()
    sealer = ph._sealer
    cb_serial = {k: v for k, v in sh.counter_block().items()}
    cb_piped = {k: v for k, v in ph.counter_block().items()}
    svc = sorted(pt.get_all_service_names())[0]
    end_ts = int(jax.device_get(ph.state.ts_max)) + 1
    same_query = (
        pt.get_trace_ids_by_name(svc, None, end_ts, 50)
        == st.get_trace_ids_by_name(svc, None, end_ts, 50)
    )
    out = {
        "spans": len(spans),
        "depth": depth,
        "capture_backlog": capture_backlog,
        "serial_spans_per_s": round(len(spans) / serial_s, 1),
        "pipelined_spans_per_s": round(len(spans) / pipelined_s, 1),
        "speedup": round(serial_s / pipelined_s, 3),
        "overlap_efficiency": round(
            (encode_s + stage_s + commit_s) / pipelined_s, 2),
        "encode_s": round(encode_s, 3),
        "stage_s": round(stage_s, 3),
        "commit_s": round(commit_s, 3),
        "prefetch_stall_s": round(stall_s, 3),
        "capture_stall_s": round(
            float(sealer.c_stall.value) if sealer else 0.0, 3),
        "windows_sealed": int(sealer.c_sealed.value) if sealer else 0,
        "recompiles_after_warmup": int(recompiles),
        "counter_blocks_identical": cb_serial == cb_piped,
        "sample_query_identical": bool(same_query),
        "ingest_dispatch_ms": _sketch_ms(ph._h_dispatch),
        "ingest_true_step_ms": _sketch_ms(ph._h_ingest),
    }
    st.close()
    pt.close()
    return out


def bench_durability(total_spans: int = 100_000):
    """Durability phase (r10 tentpole, zipkin_tpu.wal): what the
    write-ahead log costs on the ingest path and buys at recovery.
    Measures the same span stream through a plain store (baseline +
    oracle) and through WAL-attached stores at each fsync policy
    (group-commit interval = the daemon default, off, and per-batch at
    a quarter of the stream — per-append fsync is the worst case and
    needs no full-length drive to characterize), then closes the log,
    reopens it cold, and times a full-log recovery into a fresh store,
    gating bitwise identity against the uncrashed oracle. Process-
    death coverage is tests/test_crash.py; this phase puts NUMBERS on
    the contract: append overhead per policy, WAL bytes/span on disk,
    recovery spans/s."""
    import shutil
    import tempfile

    from zipkin_tpu.store import device as dev
    from zipkin_tpu.store.tpu import TpuSpanStore
    from zipkin_tpu.testing.crash import states_bitwise_equal
    from zipkin_tpu.tracegen import generate_traces
    from zipkin_tpu.wal import WriteAheadLog, recover

    cap = 1 << max(12, total_spans.bit_length() - 1)
    config = dev.StoreConfig(
        capacity=cap, ann_capacity=4 * cap, bann_capacity=2 * cap,
        max_services=64, max_span_names=256,
        max_annotation_values=512, max_binary_keys=64,
        cms_width=1 << 12, hll_p=10, quantile_buckets=512,
    )
    _log(f"durability phase: {total_spans} spans, ring 2^"
         f"{cap.bit_length() - 1}")
    spans = []
    while len(spans) < total_spans:
        spans.extend(
            s for t in generate_traces(
                n_traces=max(total_spans // 5, 64), max_depth=3,
                n_services=32,
            ) for s in t
        )
    spans = spans[:total_spans]
    chunk = 1024

    def stream(store, n=None):
        sub = spans if n is None else spans[:n]
        t0 = time.perf_counter()
        for i in range(0, len(sub), chunk):
            store.apply(sub[i:i + chunk])
        return time.perf_counter() - t0

    root = tempfile.mkdtemp(prefix="wal-bench-")
    try:
        stream(TpuSpanStore(config))  # jit warm-up (uncounted)
        oracle = TpuSpanStore(config)
        base_s = stream(oracle)

        def wal_drive(fsync, n=None, tag=""):
            store = TpuSpanStore(config)
            wal = WriteAheadLog(
                os.path.join(root, f"wal-{fsync}{tag}"), fsync=fsync)
            store.attach_wal(wal)
            dt = stream(store, n)
            wal.sync()
            return store, wal, dt

        s_int, wal_int, interval_s = wal_drive("interval")
        _, wal_off, off_s = wal_drive("off")
        n_batch = max(chunk, total_spans // 4)
        _, wal_b, batch_s = wal_drive("batch", n=n_batch)
        base_batch_s = base_s * n_batch / total_spans

        wal_stats = wal_int.stats()
        wal_dir = wal_int.directory
        for w in (wal_int, wal_off, wal_b):
            w.close()

        # Cold recovery: reopen the log (open-time torn-tail scan
        # included) and replay everything into a fresh store.
        t0 = time.perf_counter()
        wal2 = WriteAheadLog(wal_dir, fsync="off")
        rec, rstats = recover(
            None, wal2, fresh_store=lambda: TpuSpanStore(config))
        recovery_s = time.perf_counter() - t0
        identical = states_bitwise_equal(oracle.state, rec.state)
        wal2.close()
        append_ms = _sketch_ms(wal_int.h_append)
        return {
            "spans": total_spans,
            "baseline_ingest_s": round(base_s, 2),
            "wal_interval_ingest_s": round(interval_s, 2),
            "wal_off_ingest_s": round(off_s, 2),
            "wal_batch_ingest_s": round(batch_s, 2),
            "wal_batch_spans": n_batch,
            "append_overhead_interval_pct": round(
                100.0 * (interval_s - base_s) / base_s, 1),
            "append_overhead_off_pct": round(
                100.0 * (off_s - base_s) / base_s, 1),
            "append_overhead_batch_pct": round(
                100.0 * (batch_s - base_batch_s) / base_batch_s, 1),
            "wal_mb": round(wal_stats["wal_bytes"] / 1e6, 2),
            "wal_bytes_per_span": round(
                wal_stats["wal_bytes"] / total_spans, 1),
            "wal_segments": wal_stats["wal_segments"],
            "recovery_s": round(recovery_s, 2),
            "recovery_spans_per_s": round(
                rstats["replayed_spans"] / max(rstats["replay_s"],
                                               1e-9), 1),
            "replayed_records": rstats["replayed_records"],
            "recovered_identical": bool(identical),
            "wal_append_ms": append_ms,
        }
    finally:
        shutil.rmtree(root, ignore_errors=True)


def _sketch_ms(sketch) -> dict:
    """Latency sketch snapshot with the time keys scaled to ms."""
    return {
        k: (round(v * 1e3, 3)
            if k in ("sum", "mean", "stddev", "p50", "p99") and v == v
            else v)
        for k, v in sketch.snapshot().items()
    }


def bench_windows(total_spans: int = 200_000):
    """Windowed-analytics phase (r13 tentpole, aggregate/windows.py):
    what the (service × time-bucket) Moments-sketch arena costs on the
    fused ingest step and what it buys at read time. Measures (a) the
    window-on vs window-off spans/s delta — the arena's 5 extra
    scatters riding the step (store/census.py r13 bump); (b) serve
    p50/p99 for windowed_quantiles / slo_burn / latency_heatmap, all
    answered from the host mirror cells with ZERO device dispatches;
    (c) mirror-vs-device bitwise identity of the four window arrays;
    (d) exactness — windowed error/total counts equal an exact span
    scan (cell sums are exact) and the quantile estimate's rank error
    vs the true duration distribution stays inside SOLVER_RANK_TOL."""
    import numpy as np

    import jax

    from zipkin_tpu.aggregate import windows as win
    from zipkin_tpu.models.span import Annotation, Endpoint, Span
    from zipkin_tpu.store import device as dev
    from zipkin_tpu.store.tpu import TpuSpanStore

    cap = 1 << max(10, total_spans.bit_length() - 1)
    n_services = 16
    config = dev.StoreConfig(
        capacity=cap, ann_capacity=4 * cap, bann_capacity=2 * cap,
        max_services=64, max_span_names=256,
        max_annotation_values=512, max_binary_keys=64,
        cms_width=1 << 12, hll_p=10, quantile_buckets=512,
        window_seconds=60, window_buckets=64,
    )
    _log(f"windows phase: ring 2^{cap.bit_length() - 1}, "
         f"{total_spans} spans, arena {config.max_services}x"
         f"{config.window_buckets}")
    rng = np.random.default_rng(13)
    eps = [Endpoint(1 + i, 80, f"wsvc{i:02d}") for i in range(n_services)]
    base = 1_700_000_000_000_000
    # Spread first-timestamps over half the ring's retention so dozens
    # of time buckets are live; ~8% of spans carry the "error"
    # annotation convention.
    span_us = config.window_us * (config.window_buckets // 2)
    offs = rng.integers(0, span_us, total_spans)
    durs = (np.exp(rng.normal(7.0, 1.3, total_spans)).astype(np.int64)
            + 1)
    spans = []
    for i in range(total_spans):
        ep = eps[i % n_services]
        t0 = base + int(offs[i])
        anns = [Annotation(t0, "sr", ep),
                Annotation(t0 + int(durs[i]), "ss", ep)]
        if i % 12 == 0:
            anns.append(Annotation(t0 + 1, "error", ep))
        spans.append(Span(i // 4 + 1, f"op{i % 8}", i + 1, None,
                          tuple(anns), ()))
    chunk = 1024

    def stream(store):
        t0 = time.perf_counter()
        for i in range(0, len(spans), chunk):
            store.apply(spans[i:i + chunk])
        return time.perf_counter() - t0

    # (a) fused-step cost: warm both lowerings, then time each.
    cfg_off = config._replace(window_seconds=0)
    stream(TpuSpanStore(cfg_off))
    warm_on = TpuSpanStore(config)
    stream(warm_on)
    off_s = stream(TpuSpanStore(cfg_off))
    store = TpuSpanStore(config)
    on_s = stream(store)

    # (c) bitwise identity of the arena vs its mirror twins.
    st = store.state
    dev_arrays = jax.device_get(
        (st.win_epoch, st.win_counts, st.win_sums, st.win_mm))
    mir = store.sketch_mirror
    bitwise = all(np.array_equal(a, b) for a, b in zip(
        dev_arrays,
        (mir.win_epoch, mir.win_counts, mir.win_sums, mir.win_mm)))

    # (b) serve latency: all three endpoints off the mirror cells.
    svc = "wsvc01"
    qs = [0.5, 0.95, 0.99]
    lat = {"windowed_quantiles": [], "slo_burn": [], "latency_heatmap": []}
    store.windowed_quantiles(svc, qs)  # one-time numpy/solver warmup
    for _ in range(40):
        t0 = time.perf_counter()
        est = store.windowed_quantiles(svc, qs)
        lat["windowed_quantiles"].append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        burn = store.slo_burn(svc, objective=0.99)
        lat["slo_burn"].append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        heat = store.latency_heatmap(svc, bands=12)
        lat["latency_heatmap"].append(time.perf_counter() - t0)

    def pctls(samples):
        a = np.sort(samples)
        return {"p50_ms": round(float(a[len(a) // 2]) * 1e3, 3),
                "p99_ms": round(float(a[int(len(a) * 0.99)]) * 1e3, 3)}

    # (d) exactness vs the raw span stream.
    mine = [s for s in spans if (s.service_name or "") == svc]
    exact_durs = np.sort([s.duration for s in mine
                          if s.duration is not None])
    rank_err = max(
        abs(np.searchsorted(exact_durs, e) / max(len(exact_durs) - 1, 1)
            - q)
        for q, e in zip(qs, est))
    exact_errors = sum(
        1 for s in mine
        if any(a.value == "error" for a in s.annotations))
    widest = max(burn["windows"], key=lambda w: w["windowSeconds"])
    counts_exact = (widest["total"] == len(mine)
                    and widest["errors"] == exact_errors)
    out = {
        "spans": len(spans),
        "window_seconds": config.window_seconds,
        "window_buckets": config.window_buckets,
        "window_off_spans_per_s": round(len(spans) / off_s, 1),
        "window_on_spans_per_s": round(len(spans) / on_s, 1),
        "arena_overhead_pct": round((on_s / off_s - 1.0) * 100.0, 2),
        "mirror_bitwise_identical": bool(bitwise),
        "live_cells": int(mir.window_live_cells()),
        "heatmap_columns": len(heat["bucketStartsTs"]),
        "burn_error_counts_exact": bool(counts_exact),
        "quantile_rank_err": round(float(rank_err), 4),
        "solver_rank_tol": win.SOLVER_RANK_TOL,
        **{k: pctls(v) for k, v in lat.items()},
    }
    warm_on.close()
    store.close()
    return out


def bench_paged(total_spans: int = 100_000):
    """Paged-layout phase (r19 tentpole, store/paged): the end of the
    skew tax. Trace sizes in production are zipf — 1-span polls next
    to 10k-span batch jobs — and a FIFO ring must over-provision for
    the p99 trace because a long-running trace's early spans get
    overwritten by unrelated churn, leaving partial traces that
    occupy rows yet answer no complete-trace query. The paged layout
    reclaims at page granularity with trace-granular LRW (a writing
    trace keeps its whole chain fresh), so active traces stay WHOLE.

    Arms:
    (a) skewed retention — a zipf session mix (concurrent long-lived
        traces, sizes 1..10k clipped to the pool) streamed to several
        ring laps through BOTH layouts at EQUAL device memory; the
        metric is complete-trace spans retained per device byte
        (spans of traces the store still answers IN FULL), paged/ring
        ratio — the acceptance gate is >= 2x;
    (b) uniform ingest — contiguous fixed-size traces, serial and
        pipelined spans/s for both layouts; the planner must cost
        < 10% vs ring;
    (c) skewed ingest rate through the paged planner, plus the
        page-pool counters at end of stream."""
    import numpy as np

    import jax

    from zipkin_tpu.models.span import Annotation, Endpoint, Span
    from zipkin_tpu.store import device as dev
    from zipkin_tpu.store.tpu import TpuSpanStore

    cap = 1 << max(12, total_spans.bit_length() - 3)
    page_rows = 64
    config = dev.StoreConfig(
        capacity=cap, ann_capacity=4 * cap, bann_capacity=2 * cap,
        max_services=64, max_span_names=256,
        max_annotation_values=512, max_binary_keys=64,
        cms_width=1 << 12, hll_p=10, quantile_buckets=512,
        rank_path="counting",
    )
    cfg_paged = config._replace(layout="paged", page_rows=page_rows)
    _log(f"paged phase: pool 2^{cap.bit_length() - 1} x2, "
         f"{total_spans} spans, page_rows={page_rows}")
    rng = np.random.default_rng(19)
    eps = [Endpoint(1 + i, 80, f"psvc{i:02d}") for i in range(8)]
    base = 1_700_000_000_000_000

    # (a) the skewed session stream: SESSIONS concurrent long-lived
    # traces (batch jobs dribbling spans), zipf-tailed sizes floored
    # so every session's span SPREAD exceeds the ring window (~cap
    # rows) while the total active footprint fits the page pool —
    # plus a 5% stream of 1-span polls (the other end of the zipf).
    # The FIFO ring holds a window full of session partials it can
    # never answer whole; the paged store's trace-granular LRW keeps
    # the active sessions complete at the same device memory.
    SESSIONS = 16
    lo, hi = cap // 10, min(10_000, cap // 5)
    sizes = np.clip(rng.zipf(1.2, total_spans), lo, hi)
    emitted: dict = {}
    spans = []
    next_tid = 1
    next_size = iter(sizes.tolist())
    active = []
    for _ in range(SESSIONS):
        active.append([next_tid, int(next(next_size)), 0])
        next_tid += 1
    churn = rng.random(total_spans) < 0.05
    picks = rng.integers(0, SESSIONS, total_spans)
    poll_tid = 1_000_000_000
    for i in range(total_spans):
        t0 = base + i * 10
        if churn[i]:
            ep = eps[poll_tid % 8]
            spans.append(Span(poll_tid, "poll", poll_tid * 8 + 1, None,
                              (Annotation(t0, "sr", ep),
                               Annotation(t0 + 3, "ss", ep)), ()))
            emitted[poll_tid] = 1
            poll_tid += 1
            continue
        sess = active[int(picks[i])]
        tid, size, done = sess
        ep = eps[tid % 8]
        spans.append(Span(tid, f"op{done % 8}", tid * 100_000 + done + 1,
                          None, (Annotation(t0, "sr", ep),
                                 Annotation(t0 + 7, "ss", ep)), ()))
        emitted[tid] = done + 1
        sess[2] = done + 1
        if sess[2] >= size:
            active[int(picks[i])] = [next_tid, int(next(next_size)), 0]
            next_tid += 1
    chunk = 512

    def stream(store, pipelined=False):
        if pipelined:
            store.start_pipeline(8)
        t0 = time.perf_counter()
        for i in range(0, len(spans), chunk):
            store.apply(spans[i:i + chunk])
        if pipelined:
            store.drain_pipeline()
            store.stop_pipeline()
        return time.perf_counter() - t0

    def complete_spans(store) -> int:
        """Spans belonging to traces the store still answers IN FULL
        (count == every span emitted for that tid). Partial traces
        credit zero — they are the skew tax."""
        total = 0
        tids = sorted(emitted)
        for i in range(0, len(tids), 128):
            batch = tids[i:i + 128]
            for trace in store.get_spans_by_trace_ids(batch):
                if not trace:
                    continue
                tid = trace[0].trace_id
                if len(trace) == emitted[tid]:
                    total += len(trace)
        return total

    ring = TpuSpanStore(config)
    stream(ring)
    paged = TpuSpanStore(cfg_paged)
    skew_first_s = stream(paged)
    state_bytes = int(sum(
        a.size * a.dtype.itemsize
        for a in jax.tree_util.tree_leaves(ring.state)))
    ring_complete = complete_spans(ring)
    paged_complete = complete_spans(paged)
    pstats = paged.counters()
    ring.close()

    # (c) skewed ingest rate at warmed shapes.
    steady = TpuSpanStore(cfg_paged)
    skew_s = stream(steady)
    steady.close()

    # (b) uniform arm: contiguous 8-span traces, both layouts, serial
    # and pipelined (the planner rides stage 1, overlapped with the
    # device step exactly like encode).
    uni = []
    for t in range(total_spans // 8):
        ep = eps[t % 8]
        for j in range(8):
            t0 = base + t * 100 + j
            uni.append(Span(t + 1, f"uop{j}", t * 10 + j + 1, None,
                            (Annotation(t0, "sr", ep),
                             Annotation(t0 + 5, "ss", ep)), ()))

    def udrive(cfg, pipelined):
        store = TpuSpanStore(cfg)
        if pipelined:
            store.start_pipeline(8)
        t0 = time.perf_counter()
        for i in range(0, len(uni), chunk):
            store.apply(uni[i:i + chunk])
        if pipelined:
            store.drain_pipeline()
            store.stop_pipeline()
        dt = time.perf_counter() - t0
        store.close()
        return len(uni) / dt

    udrive(config, False)       # warm both lowerings
    udrive(cfg_paged, False)
    ring_uni = udrive(config, False)
    paged_uni = udrive(cfg_paged, False)
    udrive(config, True)
    udrive(cfg_paged, True)
    ring_uni_pipe = udrive(config, True)
    paged_uni_pipe = udrive(cfg_paged, True)

    out = {
        "spans": len(spans),
        "capacity": cap,
        "page_rows": page_rows,
        "sessions": SESSIONS,
        "session_spans_min_max": [int(lo), int(hi)],
        "ring_laps": round(len(spans) / cap, 1),
        "state_bytes": state_bytes,
        "ring_complete_spans": int(ring_complete),
        "paged_complete_spans": int(paged_complete),
        "ring_spans_per_mb": round(ring_complete * (1 << 20)
                                   / state_bytes, 1),
        "paged_spans_per_mb": round(paged_complete * (1 << 20)
                                    / state_bytes, 1),
        "retention_ratio": round(paged_complete
                                 / max(1, ring_complete), 2),
        "skewed_spans_per_s": round(len(spans) / skew_s, 1),
        "skewed_first_drive_spans_per_s": round(
            len(spans) / skew_first_s, 1),
        "uniform_ring_spans_per_s": round(ring_uni, 1),
        "uniform_paged_spans_per_s": round(paged_uni, 1),
        "uniform_overhead_pct": round(
            (ring_uni / paged_uni - 1.0) * 100.0, 2),
        "uniform_pipelined_ring_spans_per_s": round(ring_uni_pipe, 1),
        "uniform_pipelined_paged_spans_per_s": round(paged_uni_pipe, 1),
        "pages_active": int(pstats["pages_active"]),
        "pages_free": int(pstats["pages_free"]),
        "page_reclaims_total": int(pstats["page_reclaims_total"]),
    }
    paged.close()
    return out


def bench_replication(total_spans: int = 100_000, n_replicas: int = 3):
    """Replication phase (r15 tentpole, zipkin_tpu.replicate): what
    WAL shipping buys and costs. One WAL-attached tiered primary
    streams while (a) N device-free replicas and (b) one warm standby
    follow over the real framed-TCP ship path. Measures: replica
    staleness lag under full ingest load (records and seconds),
    failover RTO (standby drains the durable tail + promotes, bitwise
    vs the primary), aggregate sketch-tier queries/s across the
    replica fleet (the horizontal read-scaling claim), and per-replica
    apply rate (the ceiling on how fast a CPU can follow one chip)."""
    import shutil
    import tempfile
    import threading

    import numpy as np

    from zipkin_tpu.replicate import (
        Follower,
        ReplicaTarget,
        ShipClient,
        ShipServer,
        StandbyTarget,
        WalShipper,
    )
    from zipkin_tpu.replicate.protocol import config_from_dict
    from zipkin_tpu.store import device as dev
    from zipkin_tpu.store.archive import TieredSpanStore
    from zipkin_tpu.store.replica import ReplicaSpanStore
    from zipkin_tpu.store.tpu import TpuSpanStore
    from zipkin_tpu.testing.crash import states_bitwise_equal
    from zipkin_tpu.tracegen import generate_traces
    from zipkin_tpu.wal import WriteAheadLog

    cap = 1 << max(12, total_spans.bit_length() - 2)
    config = dev.StoreConfig(
        capacity=cap, ann_capacity=4 * cap, bann_capacity=2 * cap,
        max_services=64, max_span_names=256,
        max_annotation_values=512, max_binary_keys=64,
        cms_width=1 << 12, hll_p=10, quantile_buckets=512,
    )
    _log(f"replication phase: {total_spans} spans, {n_replicas} "
         f"device-free replicas + 1 warm standby")
    spans = []
    while len(spans) < total_spans:
        spans.extend(
            s for t in generate_traces(
                n_traces=max(total_spans // 5, 64), max_depth=3,
                n_services=32,
            ) for s in t
        )
    spans = spans[:total_spans]
    chunk = 1024
    root = tempfile.mkdtemp(prefix="replication-bench-")
    followers = []
    replicas = []
    server = None
    try:
        primary = TieredSpanStore(TpuSpanStore(config))
        wal = WriteAheadLog(os.path.join(root, "wal"), fsync="off")
        primary.attach_wal(wal)
        shipper = WalShipper(primary)
        server = ShipServer(shipper, host="127.0.0.1", port=0)
        port = server.server_address[1]
        server.serve_in_thread()

        for r in range(n_replicas):
            c = ShipClient("127.0.0.1", port, f"bench-replica-{r}",
                           mode="replica")
            replica = ReplicaSpanStore(config_from_dict(
                c.connect()["config"]))
            replicas.append(replica)
            followers.append(Follower(
                ReplicaTarget(replica), c,
                poll_interval_s=0.002).start())
        sc = ShipClient("127.0.0.1", port, "bench-standby",
                        mode="standby")
        sc.connect()
        standby = TpuSpanStore(config)
        f_sby = Follower(StandbyTarget(standby), sc,
                         poll_interval_s=0.002)
        followers.append(f_sby.start())

        # Full-load stream with lag sampling per batch.
        lags = []
        t0 = time.perf_counter()
        for i in range(0, len(spans), chunk):
            primary.apply(spans[i:i + chunk])
            lags.append(max(f.lag_records() for f in followers))
        ingest_s = time.perf_counter() - t0
        wal.sync()
        records_total = wal.last_seq
        s_per_record = ingest_s / max(records_total, 1)

        # Failover RTO: standby applies the durable tail + promotes.
        t0 = time.perf_counter()
        sby_ok = f_sby.drain(300.0)
        promoted = f_sby.promote()
        rto_s = time.perf_counter() - t0
        standby_bitwise = states_bitwise_equal(
            primary.hot.state, promoted.state)

        t0 = time.perf_counter()
        reps_ok = all(f.drain(300.0) for f in followers[:-1])
        replica_catch_up_s = time.perf_counter() - t0

        # Bitwise agreement at the drained frontier (replica 0 stands
        # for the fleet: all applied the identical record stream).
        a_p = primary.hot.ensure_sketch_mirror().arrays()
        mirror_bitwise = all(
            all(np.array_equal(x, y)
                for x, y in zip(a_p, rep.sketch_mirror.arrays()))
            for rep in replicas
        )
        svcs = sorted(primary.get_all_service_names())
        agree = all(
            rep.service_duration_quantiles(svc, [0.5, 0.99])
            == primary.service_duration_quantiles(svc, [0.5, 0.99])
            for rep in replicas for svc in svcs[:3]
        )

        # Aggregate replica read throughput: one thread per replica
        # hammers the sketch tier (the dashboard-fanout shape).
        reads_per_thread = 400
        counts = [0] * len(replicas)

        def read_loop(idx):
            rep = replicas[idx]
            for i in range(reads_per_thread):
                svc = svcs[i % len(svcs)]
                rep.service_duration_quantiles(svc, [0.5, 0.99])
                rep.top_annotations(svc)
                rep.estimated_unique_traces()
                counts[idx] += 3

        threads = [threading.Thread(target=read_loop, args=(i,))
                   for i in range(len(replicas))]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        fleet_s = time.perf_counter() - t0
        fleet_qps = sum(counts) / fleet_s

        lag_arr = np.asarray(lags[1:] or [0], np.int64)
        rep0 = replicas[0]
        return {
            "spans": total_spans,
            "replicas": n_replicas,
            "records_shipped": int(records_total),
            "primary_ingest_spans_per_s": round(
                total_spans / ingest_s, 1),
            "lag_records_max": int(lag_arr.max()),
            "lag_records_p50": int(np.median(lag_arr)),
            "lag_seconds_max": round(
                float(lag_arr.max()) * s_per_record, 3),
            "replica_catch_up_s": round(replica_catch_up_s, 3),
            "replica_apply_spans_per_s": round(
                rep0.spans_applied
                / max(ingest_s + replica_catch_up_s, 1e-9), 1),
            "failover_rto_s": round(max(rto_s, 1e-4), 4),
            "standby_bitwise": bool(standby_bitwise),
            "standby_caught_up": bool(sby_ok),
            "replicas_caught_up": bool(reps_ok),
            "mirror_bitwise_all_replicas": bool(mirror_bitwise),
            "sketch_answers_identical": bool(agree),
            "fleet_sketch_queries_per_s": round(fleet_qps, 1),
            "fleet_read_threads": len(replicas),
            "shipped_mb_per_follower": round(
                shipper.status()["followers"]
                ["bench-replica-0"]["shippedBytes"] / 1e6, 2),
        }
    finally:
        for f in followers:
            try:
                f.close()
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass
        for rep in replicas:
            rep.close()
        if server is not None:
            server.shutdown()
        shutil.rmtree(root, ignore_errors=True)


def bench_multichip(total_spans: int = 200_000,
                    n_shards: Optional[int] = None):
    """Multi-chip sharded serving phase (r16 tentpole,
    zipkin_tpu.parallel.shard): what the fleet buys over one chip.
    One span stream is driven through (a) a single-device store and
    (b) an N-shard ``ShardedSpanStore`` over the same per-shard
    geometry — spans/s per chip and scaling efficiency come straight
    from the pair. The read side measures aggregate queries/s under
    concurrent API load twice: serialized (one reader, one collective
    launch per query — the pre-dispatcher deployment) vs batched
    (eight readers through the cross-shard dispatcher, one launch per
    micro-window), with bitwise-identical answers required, plus the
    launch count the dispatcher saved. On the CPU harness the
    absolute rates are trend numbers; the scaling ratio, the launch
    arithmetic, and the identity bits are the portable evidence."""
    import threading

    import jax

    from zipkin_tpu.parallel.shard import ShardedSpanStore
    from zipkin_tpu.store import device as dev
    from zipkin_tpu.store.tpu import TpuSpanStore
    from zipkin_tpu.tracegen import generate_traces

    devs = jax.devices()
    if len(devs) < 2:
        return {"skipped": f"needs >=2 devices, have {len(devs)}"}
    from jax.sharding import Mesh

    n = n_shards or min(len(devs), 8)
    cap = 1 << max(12, total_spans.bit_length() - 2)
    config = dev.StoreConfig(
        capacity=cap, ann_capacity=4 * cap, bann_capacity=2 * cap,
        max_services=64, max_span_names=256,
        max_annotation_values=512, max_binary_keys=64,
        cms_width=1 << 12, hll_p=10, quantile_buckets=512,
    )
    _log(f"multichip phase: {total_spans} spans, {n} shards")
    spans = []
    while len(spans) < total_spans:
        spans.extend(
            s for t in generate_traces(
                n_traces=max(total_spans // 10, 64), max_depth=3,
                n_services=32,
            ) for s in t
        )
    spans = spans[:total_spans]
    chunk = 2048

    def stream(store):
        # First chunk warms the compile; timed from the second on.
        store.apply(spans[:chunk])
        t0 = time.perf_counter()
        for i in range(chunk, len(spans), chunk):
            store.apply(spans[i:i + chunk])
        return (len(spans) - chunk) / (time.perf_counter() - t0)

    single = TpuSpanStore(config)
    single_rate = stream(single)
    del single

    mesh = Mesh(np.array(devs[:n]), axis_names=("shard",))
    fleet = ShardedSpanStore(mesh, config, dispatch_window_s=0.004)
    try:
        fleet_rate = stream(fleet)
        with fleet.pipelined(depth=8):
            t0 = time.perf_counter()
            for i in range(0, len(spans), chunk):
                fleet.apply(spans[i:i + chunk])
        piped_rate = len(spans) / (time.perf_counter() - t0)

        # Read side: the same mixed query set, serialized then batched.
        svcs = sorted(fleet.get_all_service_names())[:8]
        end_ts = 2**62
        queries = [("q", svc) if i % 2 else ("ids", svc)
                   for i, svc in enumerate(svcs * 8)]

        def run_one(kind, svc):
            if kind == "q":
                return fleet.service_duration_quantiles(svc, [0.5, 0.99])
            return [(r.trace_id, r.timestamp)
                    for r in fleet.get_trace_ids_by_name(
                        svc, None, end_ts, 10)]

        for kind, svc in queries[:len(svcs) * 2]:
            run_one(kind, svc)  # warm both kernel families
        fleet.dispatcher.drain()

        launches0 = fleet.collective_launches()
        t0 = time.perf_counter()
        serialized = [run_one(*q) for q in queries]
        serial_s = time.perf_counter() - t0
        serial_launches = fleet.collective_launches() - launches0

        n_threads = 8
        per = len(queries) // n_threads
        batched: list = [None] * len(queries)
        barrier = threading.Barrier(n_threads + 1)

        def reader(t_idx):
            barrier.wait()
            for j in range(t_idx * per, (t_idx + 1) * per):
                batched[j] = run_one(*queries[j])

        threads = [threading.Thread(target=reader, args=(t,))
                   for t in range(n_threads)]
        for t in threads:
            t.start()
        launches0 = fleet.collective_launches()
        t0 = time.perf_counter()
        barrier.wait()
        for t in threads:
            t.join()
        batched_s = time.perf_counter() - t0
        batched_launches = fleet.collective_launches() - launches0
        dstats = fleet.dispatcher.stats()

        return {
            "shards": n,
            "spans": total_spans,
            "single_chip_spans_per_s": round(single_rate, 1),
            "fleet_spans_per_s": round(fleet_rate, 1),
            "fleet_pipelined_spans_per_s": round(piped_rate, 1),
            "fleet_spans_per_s_per_chip": round(fleet_rate / n, 1),
            "scaling_efficiency": round(
                fleet_rate / (single_rate * n), 3),
            "queries": len(queries),
            "serialized_qps": round(len(queries) / serial_s, 1),
            "batched_qps": round(len(queries) / batched_s, 1),
            "read_speedup": round(serial_s / batched_s, 2),
            "serialized_launches": int(serial_launches),
            "batched_launches": int(batched_launches),
            "dispatcher_launches_saved": dstats["launches_saved"],
            "dispatcher_max_batch": dstats["max_batch"],
            "answers_identical": serialized == batched,
        }
    finally:
        fleet.close()


def bench_checkpoint(store):
    """Checkpoint at bench scale: snapshot the
    streamed store, restore it, and require bit-identical answers to a
    small query set across the save/load boundary."""
    import shutil
    import tempfile

    from zipkin_tpu import checkpoint as ckpt
    from zipkin_tpu.store.tpu import TpuSpanStore

    state = store.state
    end_ts = int(state.ts_max) + 1
    S = store.config.max_services
    svcs = [f"svc-{i:04d}" for i in
            np.random.default_rng(13).integers(0, S, size=6)]

    def answers(st):
        out = []
        for svc in svcs:
            out.append([(i.trace_id, i.timestamp)
                        for i in st.get_trace_ids_by_name(
                            svc, None, end_ts, 10)])
        deps = st.get_dependencies()
        out.append(sorted(
            (l.parent, l.child, l.duration_moments.count)
            for l in deps.links
        )[:200])
        out.append(round(st.estimated_unique_traces(), 1))
        return out

    before = answers(store)
    # Per-run mkdtemp (unpredictable, 0700) under a fixed parent; stale
    # siblings from abandoned (watchdog-timed-out) runs — which never
    # reach this function's finally-rmtree — are swept here instead. A
    # fixed world-known path would let another local user pre-create or
    # symlink the target of our rmtree+writes (advisor r4).
    parent = os.path.join(tempfile.gettempdir(),
                          f"zk_bench_ckpt_{os.getuid()}")
    os.makedirs(parent, mode=0o700, exist_ok=True)
    st = os.lstat(parent)
    import stat as stat_mod
    if (st.st_uid != os.getuid()
            or not stat_mod.S_ISDIR(st.st_mode)
            or stat_mod.S_IMODE(st.st_mode) & 0o022):
        # Pre-created by someone else (sticky /tmp lets any user claim
        # the predictable name): don't sweep or reuse it — a foreign
        # parent owner could swap the snapshot dir between save and
        # load. Fall back to a fresh private tree, no leak-reclaim.
        parent = None
        path = tempfile.mkdtemp(prefix="zk_bench_ckpt_")
    else:
        for stale in os.listdir(parent):
            shutil.rmtree(os.path.join(parent, stale),
                          ignore_errors=True)
        path = tempfile.mkdtemp(dir=parent)
    try:
        t0 = time.perf_counter()
        # Chunked + resumable D2H: <=64MB slabs, each under its own
        # deadline with one retry; a wedged slab costs a bounded wait
        # and the staged leaves survive for the next attempt (r4: one
        # monolithic 544MB device_get hung >70 min).
        xfer = ckpt.save(store, path, chunk_deadline_s=240,
                         slab_retries=1)
        save_s = time.perf_counter() - t0
        size_mb = sum(
            f.stat().st_size for f in __import__("pathlib").Path(path)
            .rglob("*") if f.is_file()
        ) / 1e6
        t0 = time.perf_counter()
        restored = ckpt.load(path)
        load_s = time.perf_counter() - t0
        assert isinstance(restored, TpuSpanStore)
        after = answers(restored)
        del restored
    finally:
        shutil.rmtree(path, ignore_errors=True)
        # A wedged chunked save leaves its staged leaves beside the
        # path; this bench's paths are per-run mkdtemp names, so the
        # stage can never be resumed — reclaim it.
        shutil.rmtree(path + ".staging", ignore_errors=True)
    out = {
        "save_s": round(save_s, 2), "load_s": round(load_s, 2),
        "snapshot_mb": round(size_mb, 1),
        "query_parity": before == after,
        "d2h": xfer,
    }
    _log(f"checkpoint: save {save_s:.1f}s, load {load_s:.1f}s, "
         f"{size_mb:.0f}MB, parity={before == after}")
    return out


def bench_compare_kernels(total_spans: int = 10_000_000):
    """XLA scatter vs pallas VMEM-resident histogram ingest, same stream
    (the measured kernel decision)."""
    out = {}
    for use_pallas in (False, True):
        try:
            _, stats = bench_tpu_stream(
                total_spans, capacity_log2=20, n_services=256,
                batch_traces=8192, use_pallas=use_pallas,
            )
            out["pallas" if use_pallas else "xla"] = stats["spans_per_s"]
        except Exception as e:  # pallas may not lower on this backend
            out["pallas" if use_pallas else "xla"] = f"error: {e}"
    if all(isinstance(v, (int, float)) for v in out.values()):
        out["winner"] = "pallas" if out["pallas"] > out["xla"] else "xla"
    return out


def bench_ingest_matrix(spans_per_arm: int, smoke: bool = False):
    """Ingest-roofline round-2 evidence (r12): spans/s per
    (batch_spans, sort-path, scatter-path) arm, so the next on-chip
    run can pick the batch-escalation knee and certify the >=300k
    spans/s single-chip gate at the 100M config with the kernel
    choices named in the record.

    Three arm families, each a short fused-ingest stream:

    - **batch escalation** at the cert geometry (cap 2^22): sweep the
      template batch through {0.5x, 1x, 2x, 4x} of the r5-era 114688-
      span optimum — the PR 4 pipeline removed the host stalls that
      set it, so the scatter-amortization knee must be re-measured;
    - **sort path** at a mid geometry (cap 2^16, batch_traces=512 →
      ~3.6k spans ≈ ~57k concatenated index ROWS per launch) where
      the counting-rank scratch fits: argsort vs counting, same
      stream (at the cert geometry counting statically degrades to
      argsort — the scratch arithmetic in docs/PERFORMANCE.md — so
      the comparison is only measurable here);
    - **scatter path** at a small geometry (cap 2^12) where the
      unified arena fits VMEM: XLA plane scatters vs the fused pallas
      claim+scatter kernel (ops/pallas_kernels.arena_claim_scatter).

    Every arm records the ACTIVE paths (dev.active_paths), not just
    the requested ones — "auto"/"counting"/pallas degrade statically
    and the record must say what ran."""
    if smoke:
        arms = [
            dict(capacity_log2=14, n_services=64, batch_traces=256,
                 rank_path="argsort"),
            dict(capacity_log2=14, n_services=64, batch_traces=256,
                 rank_path="counting"),
            dict(capacity_log2=12, n_services=64, batch_traces=128,
                 use_pallas=True),
        ]
    else:
        arms = [
            # (a) batch escalation at the cert geometry.
            dict(batch_traces=8192),
            dict(batch_traces=16384),
            dict(batch_traces=32768),
            dict(batch_traces=65536),
            # (b) sort path, mid geometry (counting engages here).
            dict(capacity_log2=16, n_services=64, batch_traces=512,
                 rank_path="argsort"),
            dict(capacity_log2=16, n_services=64, batch_traces=512,
                 rank_path="counting"),
            # (c) scatter path, VMEM-resident arena geometry.
            dict(capacity_log2=12, n_services=64, batch_traces=128),
            dict(capacity_log2=12, n_services=64, batch_traces=128,
                 use_pallas=True),
        ]
    out = []
    for arm in arms:
        label = ",".join(f"{k}={v}" for k, v in sorted(arm.items()))
        try:
            store, stats = bench_tpu_stream(spans_per_arm, **arm)
            store = None  # free HBM before the next arm compiles
            out.append({
                "arm": arm,
                "batch_spans": stats["batch_spans"],
                "spans_per_s": stats["spans_per_s"],
                "rank_path": stats["rank_path"],
                "scatter_path": stats["scatter_path"],
                "chain": stats["chain"],
            })
            _log(f"matrix arm [{label}]: "
                 f"{stats['spans_per_s'] / 1e3:.1f}k spans/s "
                 f"(rank={stats['rank_path']}, "
                 f"scatter={stats['scatter_path']})")
        except Exception as e:  # noqa: BLE001 — one arm, not the phase
            out.append({"arm": arm, "error": repr(e)})
            _log(f"matrix arm [{label}] failed: {e!r}")
    return out


def _make_emitter(detail, get_ingest, get_sql):
    """The one-line JSON record, emitted INCREMENTALLY: printed+flushed
    after every completed phase (and mirrored to BENCH_PARTIAL.json), so
    a driver-window kill at ANY point still leaves the last phase's
    complete record on stdout. Rounds 3 and 4 both lost their headline
    numbers to an end-of-process-only print (r3: a dead backend's zero;
    r4: rc 124 mid-phase with stream+queries already measured). The
    driver parses the LAST JSON line; each emission is
    a complete, strictly-richer record."""
    def emit(phase):
        ingest, sql = get_ingest(), get_sql()
        detail["phases_complete"] = phase
        rec = {
            "metric": "ingest_throughput",
            "value": ingest["spans_per_s"] if ingest else 0.0,
            "unit": "spans/sec",
            "vs_baseline": (
                round(ingest["spans_per_s"] / sql["ingest_spans_per_s"],
                      2) if ingest and sql else 0.0
            ),
            "detail": detail,
        }
        line = json.dumps(rec)
        print(line, flush=True)
        try:
            with open("BENCH_PARTIAL.json", "w") as f:
                f.write(line + "\n")
        except OSError:
            pass
    return emit


def main():
    # SIGUSR1 → stack dump on stderr (a device call can block
    # indefinitely; this makes a stall diagnosable from outside).
    import faulthandler
    import signal

    faulthandler.register(signal.SIGUSR1)
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--compare-kernels", action="store_true")
    ap.add_argument("--spans", type=float, default=None,
                    help="TPU stream length (default 1e8, smoke 2e5)")
    ap.add_argument("--batch-traces", type=int, default=16384,
                    help="traces per template batch in the full config "
                         "(x7 spans; larger batches shrink the per-scan-"
                         "iteration floor share — tune on real hardware)")
    ap.add_argument("--batch-spans", type=int, default=0,
                    help="batch escalation: template batch size in "
                         "SPANS (overrides --batch-traces, rounded "
                         "down to whole traces; the half-ring guard "
                         "still clamps — see bench_ingest_matrix for "
                         "the sweep that picks the knee)")
    ap.add_argument("--use-pallas", action="store_true",
                    help="route the main stream through the pallas "
                         "kernels (histogram adds always; the fused "
                         "arena claim+scatter when the arena fits "
                         "VMEM — the record says which path ran)")
    ap.add_argument("--rank-path", default="auto",
                    choices=("auto", "argsort", "counting"),
                    help="index-write FIFO rank implementation for "
                         "the main stream (bitwise-identical paths; "
                         "counting degrades to argsort where its "
                         "scratch can't fit — recorded either way)")
    ap.add_argument("--no-ingest-matrix", action="store_true",
                    help="skip the (batch_spans, sort-path, scatter-"
                         "path) arm matrix phase")
    ap.add_argument("--pipeline-depth", type=int, default=8,
                    help="prefetch depth for the pipelined-ingest "
                         "phase (bounded stage-1 queue)")
    ap.add_argument("--exactness-budget", type=float, default=120.0,
                    help="wall-clock budget (s) for the index-vs-scan "
                         "exactness phase in full runs (each force_scan "
                         "replay is O(ring); round 4 spent 771s here)")
    args = ap.parse_args()

    from zipkin_tpu import compile_cache

    compile_cache.configure()
    import jax

    # This harness measures the accelerator: with no chip visible it
    # stops here — it never carries on at CPU shapes under device names.
    first = jax.devices()[0]
    if first.platform == "cpu":
        sys.exit("bench.py: no accelerator visible (jax platform is "
                 "'cpu'); refusing to benchmark the CPU backend")
    detail = {"device": {"platform": first.platform,
                         "kind": first.device_kind,
                         "count": len(jax.devices())}}
    _log(f"backend: {first.platform} / {first.device_kind} "
         f"x{len(jax.devices())}")

    # The SQL CPU reference (config #1): the baseline the ingest
    # figure is divided by.
    sql = bench_sql_baseline(total_spans=2_000 if args.smoke else 10_000)
    detail["config1_sql_cpu_reference"] = sql
    ingest = None
    emit = _make_emitter(detail, lambda: ingest, lambda: sql)
    try:
        batch_traces = (max(1, args.batch_spans // SPT)
                        if args.batch_spans > 0 else args.batch_traces)
        if args.smoke:
            store, ingest = bench_tpu_stream(
                int(args.spans or 2e5), capacity_log2=16, n_services=64,
                batch_traces=min(batch_traces, 1024),
                use_pallas=args.use_pallas, rank_path=args.rank_path,
            )
        else:
            store, ingest = bench_tpu_stream(
                int(args.spans or 1e8), batch_traces=batch_traces,
                use_pallas=args.use_pallas, rank_path=args.rank_path,
            )
        detail["config2_tpu_ingest"] = ingest
        emit("stream")
        detail["tpu_queries"] = bench_tpu_queries(
            store, reps=5 if args.smoke else 12
        )
        emit("stream+queries")
        detail["batched_queries"] = bench_batched_queries(
            store, ks=(1, 4, 16) if args.smoke else (1, 4, 16, 64),
            reps=3 if args.smoke else 5,
        )
        emit("stream+queries+batched")
        # Resident query engine (r11 tentpole): sketch-tier p50 /
        # concurrent index-tier p99 / cache-hit identity against the
        # p50<10ms & p99<50ms acceptance targets, with sketch answers
        # cross-checked against the device path on every rep.
        detail["query_engine"] = _bounded(
            lambda: bench_query_engine(
                store, reps=8 if args.smoke else 20,
                concurrency=4 if args.smoke else 8),
            timeout_s=600, label="query-engine")
        emit("stream+queries+batched+engine")
        detail["index_exactness"] = bench_exactness(
            store, n_queries=9 if args.smoke else 24,
            budget_s=None if args.smoke else args.exactness_budget,
        )
        emit("stream+queries+exactness")
        # Cold-tier paging layer (store/archive): capture overhead and
        # cold-query latency at ~4 ring laps. Bounded separately from
        # the main stream (its own small ring), so a failure here
        # can't strand the already-emitted core phases.
        detail["archive_cold_tier"] = _bounded(
            lambda: bench_archive(
                int(2e4) if args.smoke else int(4e5)),
            timeout_s=900, label="archive")
        emit("stream+queries+exactness+archive")
        # Pipelined ingest (r9 tentpole): serial vs three-stage
        # pipelined drive of the same stream, capture sealing async.
        # Bounded like the archive phase — a failure here must not
        # strand the already-emitted core phases.
        detail["pipelined_ingest"] = _bounded(
            lambda: bench_pipeline(
                int(2e4) if args.smoke else int(4e5),
                depth=args.pipeline_depth),
            timeout_s=900, label="pipeline")
        emit("stream+queries+exactness+archive+pipeline")
        # Durability (r10 tentpole, zipkin_tpu.wal): append overhead
        # per fsync policy, WAL bytes/span, cold recovery rate, and
        # bitwise recovered==oracle identity. Bounded like its
        # neighbors — a failure here must not strand the core phases.
        detail["durability_wal"] = _bounded(
            lambda: bench_durability(
                int(2e4) if args.smoke else int(2e5)),
            timeout_s=900, label="durability")
        emit("stream+queries+exactness+archive+pipeline+durability")
        # Windowed analytics (r13 tentpole, aggregate/windows.py):
        # arena fold overhead on the fused step, mirror-served
        # quantile/burn/heatmap latency, bitwise + exactness checks.
        # Bounded like its neighbors — a failure here must not strand
        # the core phases.
        detail["windowed_analytics"] = _bounded(
            lambda: bench_windows(
                int(2e4) if args.smoke else int(2e5)),
            timeout_s=900, label="windows")
        emit("stream+queries+exactness+archive+pipeline+durability"
             "+windows")
        # Paged span layout (r19 tentpole, store/paged): complete-
        # trace spans retained per device byte on a zipf session mix
        # (the >=2x skew-tax acceptance arm) + the uniform-ingest
        # planner overhead. Bounded like its neighbors.
        detail["paged_layout"] = _bounded(
            lambda: bench_paged(
                int(2e4) if args.smoke else int(2e5)),
            timeout_s=900, label="paged")
        emit("stream+queries+exactness+archive+pipeline+durability"
             "+windows+paged")
        # WAL-shipped replication (r15 tentpole, zipkin_tpu.replicate):
        # replica staleness lag under full ingest load, failover RTO,
        # aggregate sketch-tier queries/s across the device-free
        # replica fleet, bitwise agreement at the drained frontier.
        # Bounded like its neighbors.
        detail["replication"] = _bounded(
            lambda: bench_replication(
                int(2e4) if args.smoke else int(2e5),
                n_replicas=2 if args.smoke else 3),
            timeout_s=900, label="replication")
        emit("stream+queries+exactness+archive+pipeline+durability"
             "+windows+replication")
        # Multi-chip sharded serving (r16 tentpole, parallel/shard):
        # spans/s-per-chip scaling vs one chip, aggregate read q/s
        # serialized vs dispatcher-batched with the launch counts and
        # the bitwise-identity bit. Skips itself (one JSON key) on a
        # single-device backend; bounded like its neighbors.
        detail["multichip"] = _bounded(
            lambda: bench_multichip(
                int(2e4) if args.smoke else int(2e5)),
            timeout_s=900, label="multichip")
        emit("stream+queries+exactness+archive+pipeline+durability"
             "+windows+replication+multichip")
        # Ingest roofline round 2 (r12 tentpole): spans/s per
        # (batch_spans, sort-path, scatter-path) arm — the evidence
        # the batch-escalation knee and the >=300k spans/s cert read
        # from. Short per-arm streams, bounded, after the core emits
        # (the r4 lesson: never let an extra-credit phase strand the
        # headline record).
        if not args.no_ingest_matrix:
            detail["ingest_matrix"] = _bounded(
                lambda: bench_ingest_matrix(
                    int(1e5) if args.smoke else int(1e7),
                    smoke=args.smoke),
                timeout_s=2400, label="ingest-matrix")
            emit("core+matrix")
        # The XLA-vs-pallas kernel decision was measured and recorded in
        # round 4 (xla 158.6k vs pallas 155.0k spans/s, earlier code);
        # re-measuring it on every full run cost two extra compile+
        # stream cycles and was exactly where the round-4 driver window
        # ran out. It now runs only on explicit request.
        if args.compare_kernels:
            detail["compare_kernels"] = bench_compare_kernels(
                total_spans=int(2e5) if args.smoke else int(1e7)
            )
            emit("stream+queries+exactness+compare")
        # Checkpoint-at-scale runs under a watchdog: the snapshot is
        # a multi-GB device_get, and a hung transfer must cost a
        # bounded wait and a failed run — never an unbounded one (the
        # headline record is already emitted above either way).
        # 1200s is several times the round-4 save+load+replay (~320s,
        # earlier code, not re-measured); a timeout also suppresses
        # the 1B attempt below.
        ck = _bounded(lambda: bench_checkpoint(store), timeout_s=1200,
                      label="checkpoint")
        detail["checkpoint_at_scale"] = ck
        emit("core+checkpoint")
        ck_wedged = isinstance(ck, dict) and "timed_out_s" in ck
        # The BASELINE north star: 1B spans ingested and queried on one
        # chip. Attempt it automatically whenever the measured 100M
        # throughput makes 1e9 tractable (>= 0.7M spans/s ⇒ <= ~24 min
        # of streaming) — so an unattended end-of-round run carries the
        # evidence, not just a hand-driven session. Skipped when the
        # checkpoint watchdog fired: the abandoned transfer would
        # strand the (unbounded) 1e9 stream behind it.
        if (not args.smoke and args.spans is None and not ck_wedged
                and ingest["spans_per_s"] >= 7e5):
            store = None  # free HBM before the 1e9 stream
            _log(f"1B attempt: {ingest['spans_per_s'] / 1e6:.2f}M "
                 f"spans/s makes 1e9 tractable; streaming")
            try:
                # Extra-credit run: its failure must not mark the
                # completed core benchmark as a TPU-path failure.
                store1b, stats1b = bench_tpu_stream(
                    int(1e9), batch_traces=args.batch_traces
                )
                detail["config2b_1B_ingest"] = stats1b
                emit("core+1B-stream")
                detail["tpu_queries_1B"] = bench_tpu_queries(
                    store1b, reps=8
                )
                emit("core+1B-stream+1B-queries")
                detail["exactness_1B"] = bench_exactness(
                    store1b, n_queries=12,
                    budget_s=args.exactness_budget,
                )
                del store1b
            except Exception as e:  # noqa: BLE001
                _log(f"1B attempt failed: {e!r}")
                detail["tpu_1b_error"] = repr(e)
    except Exception as e:  # noqa: BLE001 — emit a record either way
        _log(f"TPU path failed: {e!r}")
        detail["tpu_error"] = repr(e)
    # The final line must stay truthful about how far the run got: on
    # the failure path, re-emitting "all" would claim phases that never
    # ran (the driver parses the LAST line).
    failed = sorted(
        k for k, v in detail.items()
        if isinstance(v, dict) and ("error" in v or "timed_out_s" in v))
    if "tpu_error" in detail:
        emit(f"aborted-after:{detail.get('phases_complete', 'none')}")
    else:
        emit("all")
    # A failed phase is a failed run: the record above says how far it
    # got, the exit code says it did not get there cleanly.
    if "tpu_error" in detail or failed:
        _log(f"failed: {detail.get('tpu_error') or failed}")
        sys.exit(1)


if __name__ == "__main__":
    main()
