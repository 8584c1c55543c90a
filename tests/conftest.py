"""Test env: force an 8-device virtual CPU mesh before jax is used.

Mirrors the reference's approach of testing multi-node behavior without a
cluster (FakeCassandra / minicluster, SURVEY.md §4): we test multi-chip
sharding on a host-simulated device mesh.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from __graft_entry__ import _force_cpu_mesh_env  # noqa: E402

_force_cpu_mesh_env(8, os.environ)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

# ---------------------------------------------------------------------------
# Fast lane / slow lane (round 4: the full suite reached
# 33 min on CPU and slow suites rot). Tests measured >= ~8 s (soaks,
# eviction laps, sharded conformance, checkpoint round-trips) are
# marked ``slow`` here by FUNCTION name — one maintainable list instead
# of decorators scattered over ten files. The default lane excludes
# them (pyproject addopts) and runs in ~2.5 min; the full lane is
#     python -m pytest tests/ -m ""
# and stays the bar for index/trust/parallel changes (README).
# ---------------------------------------------------------------------------

_SLOW_TESTS = {
    "test_tracegen_parity",
    "test_save_restore_roundtrip",
    "test_tracegen_main_tpu_roundtrip",
    "test_pinned_traces_survive_checkpoint_restart",
    "test_sharded_checkpoint_roundtrip",
    "test_sharded_checkpoint_wal_tail_recovery",
    "test_sharded_pipelined_ingest_bitwise_matches_serial",
    "test_sharded_legacy_snapshot_migrates",
    "test_dependencies_honor_time_window",
    "test_sharded_dependencies_window",
    "test_moments_numerically_stable_for_large_means",
    "test_chained_ingest_steps_bitwise_matches_sequential",
    "test_same_batches_bitwise_same_state",
    "test_store_chained_writes_bitwise_match_single",
    "test_dictionary_overflow_service_routes_to_scan",
    "test_hot_trace_beyond_bucket_depth_falls_back",
    "test_index_matches_scan_by_service",
    "test_middle_host_poison_self_heals_after_eviction",
    "test_pre_index_snapshot_poisons_trust",
    "test_pre_rev7_snapshot_disables_key_table",
    "test_sparse_key_under_hot_bucket_stays_on_fast_path",
    "test_trace_membership_after_eviction",
    "test_trace_membership_fast_path_matches_scan",
    "test_wrapped_bucket_falls_back_to_scan",
    "test_far_future_timestamps_stay_exact",
    "test_ts_watermark_coarse_boundary_window_stays_exact",
    "test_sharded_dep_links_survive_eviction",
    "test_sharded_dep_moments_match_single_store",
    "test_sharded_dictionary_overflow_service_routes_to_scan",
    "test_sharded_hll_is_union",
    "test_sharded_ingest_totals",
    "test_sharded_multi_query_matches_singular",
    "test_sharded_query_roundtrip",
    "test_sharded_store_conformance",
    "test_summary_dep_compaction_parity",
    "test_no_slices_by_service",
    "test_concurrent_sharded_ingest_and_query",
    "test_cross_batch_links_survive_archive",
    "test_dependency_links_from_streaming_join",
    "test_oversized_batch_rejected_but_apply_chunks",
    "test_single_span_annotation_overflow_truncated",
    "test_sketches_survive_eviction",
    "test_hot_trace_candidate_escalation",
    "test_pinned_trace_survives_ring_eviction",
    "test_sharded_pinned_trace_survives_eviction",
    "test_feeds_tpu_store",
    "test_chunked_save_resumes_after_wedged_transfer",
    "test_stale_staging_discarded_after_writes",
    "test_sweep_between_attempts_discards_staging",
    "test_chunked_save_slabs_large_leaves",
    "test_wedged_slab_fails_fast_with_bounded_lock_hold",
    "test_tiered_checkpoint_roundtrip",
    "test_two_process_distributed_routing",
    # Cold-tier deep coverage beyond the fast-lane acceptance drive
    # (TestTieredConformance stays fast; these re-build tiered stores).
    "test_bytes_roundtrip_bit_exact",
    "test_compression_actually_compresses",
    "test_merge_zone_is_monoidal",
    "test_contiguous_coverage_no_gaps",
    "test_captured_spans_are_complete",
    "test_multi_matches_singular",
    "test_service_and_span_name_catalogs",
    "test_pin_through_tiers_banks_cold_rows",
    "test_capture_now_flushes_resident_window",
    "test_tiered_store_conformance",
    "test_annotation_heavy_chained_writes_stay_complete",
    "test_transient_pull_failure_is_retried_not_skipped",
    "test_query_client_methods",
    # Pipelined-ingest stress lane (tests/test_pipeline.py): the fast
    # lane keeps the bitwise pipelined==serial gate, the zero-recompile
    # gate, lifecycle/error surfacing, and the metric split; these
    # three re-drive tiered stores / sleep on a slow sealer / run a
    # threaded save, which the fast-lane wall budget can't afford.
    "test_pipelined_capture_matches_inline_sealing",
    "test_capture_backpressure_bounds_memory",
    "test_checkpoint_during_pipelined_ingest",
    # Crash-injection matrix (tests/test_crash.py): each case SIGKILLs
    # a real child drive, then recovers + re-drives an oracle. The
    # after-append smoke stays in tier-1; the rest of the kill-point
    # matrix (checkpoint swaps, truncation, cold-tier sealing) is here.
    "test_crash_before_append_loses_only_the_unacked_batch",
    "test_crash_after_commit_before_ack",
    "test_crash_mid_first_checkpoint_recovers_from_wal_alone",
    "test_crash_mid_second_checkpoint_falls_back_to_old",
    "test_crash_mid_truncate_leaves_recoverable_suffix",
    "test_crash_mid_seal_replays_capture_and_cold_tier",
    "test_crash_mid_seal_with_checkpoint",
    "test_clean_child_exits_zero",
    # Windowed-analytics deep sweeps (tests/test_windows.py): tier-1
    # keeps cell exactness, ring-wrap, boundary, solver, resync and
    # API gates; the multi-lap fuzz sweep and the checkpoint
    # round-trip ride the slow lane (bench_smoke's windows phase
    # already smoke-gates mirror bitwise identity every tier-1 run).
    "test_window_ring_wrap_deep_sweep",
    "test_pre_rev14_checkpoint_restores_empty_arena",
    # Replication deep coverage (tests/test_replication.py): tier-1
    # keeps the durable-only ship bound, gap/idempotency, standby
    # promote, and the pre-rev-14 cold-resync compat path, and
    # bench_smoke's replication phase smoke-gates replica bitwise
    # agreement + RTO every tier-1 run; the full agreement sweep, the
    # TCP anchor-bootstrap drive, and the retention soak re-drive
    # multi-thousand-span stores the fast-lane wall budget can't
    # afford (the crash-during-ship matrix is marked slow directly).
    "test_replica_bitwise_agreement_at_fixed_frontier",
    "test_tcp_follow_and_anchor_bootstrap",
    "test_replica_retention_drops_old_segments",
    "test_standby_follow_promote_bitwise",
    # Paged-layout deep coverage (tests/test_paged.py): tier-1 keeps
    # the SPI conformance sweep, planner geometry guards, the reclaim
    # fuzz, rev-18 + pre-18 checkpoint compat, and WAL-replay bitwise;
    # bench_smoke's paged phase gates
    # census arithmetic, ring-vs-paged bitwise parity and the
    # zero-recompile bound every tier-1 run, so the long skewed-stream
    # parity drive, the tiered eviction/capture drive, the mirror
    # sweep, and the counting-rank census build ride here.
    "test_query_parity_vs_ring_skewed_stream",
    "test_tiered_parity_through_eviction_and_capture",
    "test_mirror_is_layout_independent",
    "test_paged_counters_and_census_budget",
}


# The suite's CPU-hungriest files (TPU compiles for a described chip; a
# daemon child plus a g++ build). They run after everything else, so
# they do not starve the tests that wait on threads (bench_smoke's
# barrier-released burst has a 0.5 s window to land in one batch). A
# stable sort: every xdist worker collects the same order.
_LAST_FILES = {"test_chip_compile.py", "test_chip_smoke.py"}


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.originalname in _SLOW_TESTS or item.name in _SLOW_TESTS:
            item.add_marker(pytest.mark.slow)
    items.sort(key=lambda item: item.path.name in _LAST_FILES)
