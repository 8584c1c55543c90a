"""Tier-1 structure gate (scripts/bench_smoke.py): one run of the
script, one case per phase of its record, so a failure in one phase
hides none of the others.

Per-kernel overhead dominates the target device class (NOTES_r03 §3);
the r6 unified index arena exists to cut scatter/sort launches per
batch, and the r12 counting-sort rank path deleted the last hot-path
sort. The ceilings live in ONE place — ``zipkin_tpu.store.census`` —
consumed here and by the smoke script, so a path change updates
exactly one number (raise one only with a PERF.md entry explaining what
bought the extra launches). r5 split-design baseline: 101 scatters /
6 sorts / 80 gathers; r6: 95/5/79; r12: 95/4/79; PR 26 (arena
planes): 95/4/84; PR 30 (ring windows): 54/4/84.

Every gate is a count, a census, an identity or a recompile delta. No
case asserts a CPU wall-clock time, rate or overhead: the gates that
did (WAL append overhead <= 10 %, lineage overhead <= 1.05, sketch p50
< 10 ms, index p99 < 250 ms, the mirror delta's share of encode, the
lint's 30 s) kept tier-1 red on a loaded box and proved nothing about
the chip. Their replacement is measured there: ROADMAP.md S11's on/off
pairs of cells.
"""

import json
import subprocess
import sys

import pytest

from zipkin_tpu.store.census import (
    ARGSORT_STEP_SORTS,
    BASE_STEP_GATHERS,
    BASE_STEP_SCATTERS,
    BASE_STEP_SORTS,
    MAX_STEP_SORTS,
    expected_census,
)


@pytest.fixture(scope="module")
def rec():
    """The record of ONE run of the script (``--dist loadfile`` keeps
    this file on one worker, so it runs once a suite)."""
    proc = subprocess.run(
        [sys.executable, "scripts/bench_smoke.py", "--spans", "2000",
         "--k", "4"],
        capture_output=True, text=True, timeout=780,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = proc.stdout.strip().splitlines()[-1]
    record = json.loads(line)  # exactly one JSON line
    assert record["metric"] == "bench_smoke"
    return record


def _base_census(rec):
    assert rec["spans"] > 0
    # The index-family step-count gate — measured WITH telemetry wired
    # (the store registers its obs metrics and the counter block is
    # fetched), so a device counter fetch that grew the step would
    # trip here. Default config = window arena off = BASE lowering.
    assert rec["step_scatters"] <= BASE_STEP_SCATTERS, rec
    assert rec["step_sorts"] <= BASE_STEP_SORTS, rec
    assert rec["step_gathers"] <= BASE_STEP_GATHERS, rec


def _telemetry(rec):
    # The telemetry counter block itself must lower as a pure read.
    tel = rec["telemetry"]
    assert tel["counter_block_scatters"] == 0
    assert tel["counter_block_sorts"] == 0
    # spans_seen counts the warm-up step too, so >= the counted spans.
    assert tel["counter_block"]["spans_seen"] >= rec["spans"]
    assert tel["counter_block"]["ring_occupancy"] > 0


def _multi_query(rec):
    # Batched-query phase ran and agreed with serial execution.
    mq = rec["multi_query"]
    assert mq["k"] == 4 and mq["identical"] is True


def _archive(rec):
    # Archive phase: capture -> compact -> cold query identity vs the
    # memory-store oracle, with eviction capture leaving the fused
    # ingest step's op census UNTOUCHED (the tier-1 gate the cold tier
    # must hold: capture is a separate read-only launch).
    ar = rec["archive"]
    assert ar["identical"] is True
    assert ar["segments_written"] >= 1
    assert ar["compactions"] >= 1
    assert ar["segments_pruned"] >= 1
    assert ar["cold_compression_ratio"] > 1.5
    # The capture claim: a store with an eviction sink lowers the
    # fused step IDENTICALLY to a sink-less one. (The archive phase's
    # tiny ring takes the exact small-store watermark path, so its
    # absolute counts differ from the canonical-shape ceilings above —
    # equality is the invariant here.)
    assert (ar["step_census_with_capture"]
            == ar["step_census_plain"]), ar


def _pipeline(rec):
    # Pipelined-ingest phase (r9 tentpole): the three-stage pipeline
    # must land a bitwise-identical device state AND an identical cold
    # tier, a warmed steady state must perform ZERO jit recompiles
    # (pow2 staging buckets only hit cached entries), H2D staging must
    # add zero ops to the fused step's lowering (its census with
    # device-resident args equals the host-array census — the
    # step_scatters/sorts/gathers ceilings above were already measured
    # with the obs layer wired), and ingest must never have stalled on
    # capture sealing at the phase's generous backlog (deliberate
    # backpressure is exercised in tests/test_pipeline.py).
    pp = rec["pipeline"]
    assert pp["identical"] is True, pp
    assert pp["recompiles_after_warmup"] == 0, pp
    assert pp["staging_census_equal"] is True, pp
    assert pp["capture_stall_s"] == 0, pp
    assert pp["windows_sealed"] >= 1, pp


def _wal(rec):
    # Durability phase (r10 tentpole): a full-log replay into a fresh
    # store must land a BITWISE identical state (the half of the
    # ack-after-append contract a live process can prove without
    # dying — SIGKILL coverage is tests/test_crash.py), and journaling
    # must add zero jit recompiles in steady state and replay zero
    # more. The append's cost is the chip's to say: ROADMAP.md S11's
    # WAL pair.
    w = rec["wal"]
    assert w["replay_identical"] is True, w
    assert w["steady_state_recompiles"] == 0, w
    assert w["replay_recompiles"] == 0, w
    assert w["replayed_records"] >= 1, w
    assert w["wal_bytes_per_span"] > 0, w


def _query(rec):
    # Resident-query-engine phase (r11 tentpole): sketch-tier answers
    # must be IDENTICAL to the device read path's; the steady-state
    # query loop must perform ZERO jit recompiles (the resident
    # programs stay resident); cache hits must be bitwise-equal to
    # cold answers and an ingest commit must invalidate precisely (the
    # frontier-keyed re-answer equals a fresh store read). What a tier
    # costs waits for a cell with reads: ROADMAP.md S8.
    q = rec["query"]
    assert q["sketch_identical"] is True, q
    assert q["steady_recompiles"] == 0, q
    assert q["cache_hit_identical"] is True, q
    assert q["cache_invalidation_exact"] is True, q
    assert q["cache_hits"] >= 1 and q["sketch_answers"] >= 1, q


def _ingest_structure(rec):
    # Ingest-structure phase (r12 tentpole): the counting-sort rank
    # path must lower with strictly fewer sorts than the argsort path
    # (the deleted O(N log N) entry cost, structurally — store-level
    # bitwise identity between the paths is fuzz-gated in
    # tests/test_rank_paths.py); a batch-escalated geometry must
    # perform ZERO steady-state recompiles through the pipeline once
    # warmed.
    ing = rec["ingest_structure"]
    assert ing["rank_path_counting_cfg"] == ["counting"], ing
    assert ing["rank_path_argsort_cfg"] == ["argsort"], ing
    assert ing["census_counting"]["sort"] < MAX_STEP_SORTS + 1, ing
    assert ing["census_counting"]["sort"] < ARGSORT_STEP_SORTS, ing
    assert ing["census_argsort"]["sort"] <= ARGSORT_STEP_SORTS, ing
    assert (ing["census_counting"]["scatter"]
            <= ing["census_argsort"]["scatter"]), ing
    assert (ing["census_counting"]["gather"]
            <= ing["census_argsort"]["gather"]), ing
    assert ing["rank_path_counting"] == 1.0, ing
    # Every ring of the ring layout is written as a window (PR 30).
    assert ing["ring_write_cfg"] == [
        "ann:window", "bann:window", "pend:window", "span:window"], ing
    assert ing["ring_write_window"] == 4.0, ing
    assert ing["recompiles_after_batch_escalation"] == 0, ing
    assert ing["escalated_batch_spans_limit"] == 512.0, ing


def _census_ceilings(rec):
    # The ceilings the smoke JSON carries must be the census module's
    # (one definition site — this test would catch a re-hard-coding).
    # The main stream runs the library default (window arena OFF), so
    # it carries the BASE ceilings.
    assert rec["census_ceilings"] == {
        "scatter": BASE_STEP_SCATTERS, "sort": BASE_STEP_SORTS,
        "gather": BASE_STEP_GATHERS,
    }


def _windows(rec):
    # Windowed-analytics phase (r13 tentpole): the arena's fused-step
    # cost is exactly the gated census bump (the window-off lowering
    # stays at the BASE counts), mirror and device window cells are
    # BITWISE identical through serial and pipelined drives, the
    # window update adds zero steady-state recompiles, and the
    # sketch-tier windowed quantile answers inside the documented
    # solver rank tolerance.
    w = rec["windows"]
    ws, wo, wg = expected_census("+WINDOW")
    assert w["census_window_on"] == {
        "scatter": ws, "sort": wo, "gather": wg,
    }, w
    assert w["census_window_off"] == {
        "scatter": BASE_STEP_SCATTERS, "sort": BASE_STEP_SORTS,
        "gather": BASE_STEP_GATHERS,
    }, w
    assert w["mirror_bitwise"] is True, w
    assert w["pipelined_bitwise"] is True, w
    assert w["recompiles_steady_state"] == 0, w
    assert w["quantile_rank_err"] <= w["solver_rank_tol"], w
    assert w["burn_errors"] >= 1, w
    assert w["heatmap_columns"] >= 1, w
    assert w["window_spans_folded"] > 0, w


def _paged(rec):
    # Paged-layout phase (r19 tentpole): the paged fused-step lowering
    # must cost EXACTLY the gated census bump (the ring lowering stays
    # at BASE), queries through the paged layout must answer BITWISE
    # identical to a ring store fed the same skewed stream (whole-trace
    # reads and id lookups), and re-driving warmed shapes through the
    # ingest pipeline must perform ZERO recompiles (page claims are
    # host-side planner work; pad buckets alone pick compiled
    # variants). Retention per byte: not measured (no cell runs the
    # layout, ROADMAP.md D3).
    ps, po, pg = expected_census("+PAGED")
    bs2, bo2, bg2 = expected_census()
    pg_rec = rec["paged"]
    assert pg_rec["census_paged_on"] == {
        "scatter": ps, "sort": po, "gather": pg,
    }, pg_rec
    assert pg_rec["census_paged_off"] == {
        "scatter": bs2, "sort": bo2, "gather": bg2,
    }, pg_rec
    assert pg_rec["query_parity_bitwise"] is True, pg_rec
    assert pg_rec["ids_parity_bitwise"] is True, pg_rec
    assert pg_rec["recompiles_steady_state"] == 0, pg_rec
    assert pg_rec["pages_active"] >= 1, pg_rec


def _replication(rec):
    # Replication phase (r15 tentpole): a device-free ReplicaSpanStore
    # fed only shipped WAL records over the real framed-TCP ship path
    # must answer the sketch tier and row reads BITWISE identical to
    # the primary at the same applied frontier (mirror arrays equal
    # element-for-element), the whole replication stream must add
    # ZERO jit compiles (the replica is device-free; the warm standby
    # replays into already-compiled shapes), the standby must land a
    # bitwise-equal device state and be promoted, the follower must
    # catch up to lag 0 under full ingest load, and its cursor must be
    # pinned in the WAL's retention registry. Time to fail over: not
    # measured (ROADMAP.md R6).
    rep = rec["replication"]
    assert rep["replica_mirror_bitwise"] is True, rep
    assert rep["replica_answers_identical"] is True, rep
    assert rep["replication_recompiles"] == 0, rep
    assert rep["standby_bitwise"] is True, rep
    assert rep["caught_up"] is True, rep
    assert rep["records_shipped"] >= 1, rep
    assert rep["shipped_bytes"] > 0, rep
    assert rep["follower_cursor_pinned"] is True, rep


def _sharded(rec):
    # Sharded-serving phase (r16 tentpole): a 2-shard fleet on the
    # virtual mesh must fuse a barrier-released burst of 8 concurrent
    # reads through the cross-shard dispatcher into AT MOST the two
    # collective launches the design budgets (one fused catalog
    # bundle + one multi-probe kernel), answer them BITWISE identical
    # to serialized re-execution, add ZERO jit recompiles in steady
    # state (the mapped kernels stay resident; batching only changes
    # who launches them), and answer the fleet sketch tier bitwise
    # against a single-device oracle fed the same spans (name-aligned
    # histogram rows + identical HLL registers).
    sh = rec["sharded"]
    assert "skipped" not in sh, sh
    assert sh["shards"] == 2, sh
    assert sh["identical"] is True, sh
    assert sh["errors"] == [], sh
    assert sh["burst_launches"] <= 2, sh
    assert sh["steady_state_recompiles"] == 0, sh
    assert sh["dispatcher_launches_saved"] >= 6, sh
    assert sh["fleet_hist_rows_bitwise"] is True, sh
    assert sh["fleet_hll_bitwise"] is True, sh
    assert sh["service_names_identical"] is True, sh


def _fleet_obs(rec):
    # Fleet-observability phase (r17 tentpole): a live primary+
    # follower ship pair under ingest must land ONE causally-linked
    # self-trace spanning encode → WAL append → fsync → ship →
    # follower apply in the primary's own store with verified parent
    # ids; the federated scrape must carry both processes label-
    # distinguished with values bitwise identical to each process's
    # own scrape; the watchdog must fire on an injected parked-fsync
    # error and clear with it; and self-tracing at the production
    # sampling cadence must add ZERO new device launches (compile
    # delta 0, step census equal). Its cost in ingest time is the
    # chip's to say: ROADMAP.md S11.
    fo = rec["fleet_obs"]
    assert fo["trace_roundtrip"] is True, fo
    assert fo["parent_ids_ok"] is True, fo
    assert fo["federation_labels_ok"] is True, fo
    assert fo["federation_bitwise"] is True, fo
    assert fo["visible_lag_recorded"] is True, fo
    assert fo["watchdog_fired"] is True, fo
    assert fo["watchdog_cleared"] is True, fo
    assert fo["lineage_steady_state_compiles"] == 0, fo
    assert fo["census_equal"] is True, fo
    assert fo["fleet_processes"] == 2, fo


def _lint(rec):
    # graftlint phase: the concurrency/JAX-hazard analyzer must cover
    # the whole package and find ZERO findings not in the checked-in
    # baseline (the fixture-corpus sensitivity pins live in
    # tests/test_analysis.py; this gates the smoke wiring end-to-end).
    lint = rec["lint"]
    assert lint["findings_new"] == 0, lint
    assert lint["files"] >= 80, lint
    assert lint["locks"] >= 25, lint


_PHASES = {
    "base_census": _base_census,
    "telemetry": _telemetry,
    "multi_query": _multi_query,
    "archive": _archive,
    "pipeline": _pipeline,
    "wal": _wal,
    "query": _query,
    "ingest_structure": _ingest_structure,
    "census_ceilings": _census_ceilings,
    "windows": _windows,
    "paged": _paged,
    "replication": _replication,
    "sharded": _sharded,
    "fleet_obs": _fleet_obs,
    "lint": _lint,
}


@pytest.mark.parametrize("phase", list(_PHASES))
def test_bench_smoke(rec, phase):
    _PHASES[phase](rec)
