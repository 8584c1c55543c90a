"""Paged span layout (r19): conformance, bitwise ring parity, page
reclaim under wrap, rev-18 checkpoint and WAL replay determinism.

The layout contract under test (docs/STORAGE_TIERS.md): spans land in
fixed ``page_rows`` device pages claimed from a free list during the
fused ingest step, chained per trace through the host page table
(store/paged.PagePlanner), with gids epoch-encoded so every ring-scan
kernel keeps working unchanged. Everything observable — query answers,
checkpoint state, WAL recovery — must be bitwise indistinguishable
from what the stream's content dictates, never from page placement.
"""

import json
import os

import jax
import numpy as np
import pytest

from zipkin_tpu import checkpoint
from zipkin_tpu.models.span import Annotation, BinaryAnnotation, Endpoint, Span
from zipkin_tpu.store.census import expected_census
from zipkin_tpu.store.device import StoreConfig
from zipkin_tpu.store.paged import PagePlanner
from zipkin_tpu.store.tpu import TpuSpanStore
from zipkin_tpu.testing.conformance import (
    conformance_test_names,
    run_conformance_test,
)
from zipkin_tpu.testing.crash import states_bitwise_equal
from zipkin_tpu.wal import WriteAheadLog, recover, replay_into

CFG_RING = StoreConfig(
    capacity=1 << 10, ann_capacity=1 << 12, bann_capacity=1 << 11,
    max_services=32, max_span_names=128, max_annotation_values=256,
    max_binary_keys=64, cms_width=1 << 10, hll_p=8,
    quantile_buckets=512,
)
# 1024 / 128 = 8 pages — the planner's minimum pool.
CFG_PAGED = CFG_RING._replace(layout="paged", page_rows=128)

BASE_TS = 1_700_000_000_000_000


def _spans_for(tid: int, n: int, svc: str = "psvc") -> list:
    """n spans of one trace, unique span ids, two annotations each."""
    ep = Endpoint(10, 80, svc)
    out = []
    for j in range(n):
        t0 = BASE_TS + tid * 1000 + j
        out.append(Span(
            tid, f"op{j % 4}", tid * 100_000 + j + 1, None,
            (Annotation(t0, "sr", ep), Annotation(t0 + 7, "ss", ep)),
            (BinaryAnnotation("k", b"v", host=ep),),
        ))
    return out


def _skewed_stream(seed: int, total: int, max_size: int = 64):
    """Zipf-sized traces (1-span polls to page-filling batch traces)
    interleaved — the shape the paged layout exists for. Returns
    (spans, {tid: n_spans})."""
    rng = np.random.default_rng(seed)
    traces, sizes = [], {}
    tid, count = 1, 0
    while count < total:
        n = min(int(rng.zipf(1.6)), max_size)
        traces.append(_spans_for(tid, n, svc=f"psvc{tid % 3}"))
        sizes[tid] = n
        count += n
        tid += 1
    flat = [s for tr in traces for s in tr]
    return flat, sizes


def _drive(store, spans, batch=200):
    for i in range(0, len(spans), batch):
        store.apply(spans[i:i + batch])


# ---------------------------------------------------------------------------
# Conformance: the paged layout is a SpanStore like any other
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", conformance_test_names())
def test_paged_conformance(name):
    run_conformance_test(name, lambda: TpuSpanStore(CFG_PAGED))


# ---------------------------------------------------------------------------
# Bitwise query parity vs the ring layout
# ---------------------------------------------------------------------------


def test_query_parity_vs_ring_skewed_stream():
    """Whole-trace reads and id lookups answer IDENTICALLY through
    both layouts on a skewed stream (no wrap, so both retain all) —
    page placement must never leak into query results."""
    spans, sizes = _skewed_stream(seed=11, total=600)
    ring = TpuSpanStore(CFG_RING)
    paged = TpuSpanStore(CFG_PAGED)
    _drive(ring, spans)
    _drive(paged, spans)
    # Precondition: neither layout dropped anything. The paged pool
    # fragments (a 64-span trace pins a half-filled exclusive page),
    # so "fits the ring" does not imply "fits the pages" — the stream
    # above is sized to fit BOTH, and this guards the sizing.
    assert paged._planner.stats()["page_reclaims"] == 0

    for tid in sizes:
        assert (ring.get_spans_by_trace_ids([tid])
                == paged.get_spans_by_trace_ids([tid])), tid
    # Batched multi-trace reads through the shared page list too
    # (small traces share pages; the gather must filter co-tenants).
    some = sorted(sizes)[:48]
    assert (ring.get_spans_by_trace_ids(some)
            == paged.get_spans_by_trace_ids(some))

    end_ts = BASE_TS + (len(sizes) + 2) * 1000 + 10_000
    key = lambda x: (x.trace_id, x.timestamp)  # noqa: E731
    for i in range(3):
        assert (sorted(ring.get_trace_ids_by_name(
                    f"psvc{i}", None, end_ts, 200), key=key)
                == sorted(paged.get_trace_ids_by_name(
                    f"psvc{i}", None, end_ts, 200), key=key)), i


def test_tiered_parity_through_eviction_and_capture():
    """Past wrap, reclaimed pages are captured into the cold tier
    BEFORE their rows are overwritten — so a tiered paged store reads
    back every trace COMPLETE, exactly like the tiered ring does, even
    though the two layouts evict in a different order."""
    from zipkin_tpu.store.archive import ArchiveParams, TieredSpanStore

    def tiered(cfg):
        hot = TpuSpanStore(cfg)
        return TieredSpanStore(hot, params=ArchiveParams.for_config(
            hot.config, compact_fanin=2,
            small_span_limit=hot.config.capacity,
            bloom_bits=1 << 12, cms_width=1 << 10, hll_p=6))

    spans, sizes = _skewed_stream(seed=23, total=3 * CFG_RING.capacity)
    tr = tiered(CFG_RING)
    tp = tiered(CFG_PAGED)
    _drive(tr, spans)
    _drive(tp, spans)

    sample = sorted(sizes)[::7]
    got_r = tr.get_spans_by_trace_ids(sample)
    got_p = tp.get_spans_by_trace_ids(sample)
    for tid, spans_r, spans_p in zip(sample, got_r, got_p):
        want = sorted(s.id for s in _spans_for(tid, sizes[tid]))
        assert sorted(s.id for s in spans_r) == want, tid
        assert sorted(s.id for s in spans_p) == want, tid


def test_mirror_is_layout_independent():
    """The sketch mirror folds batch CONTENT only (store/mirror.py's
    delta_of contract) — ring and paged drives of the same stream must
    leave every mirrored array element-equal, wrap included."""
    spans, _ = _skewed_stream(seed=31, total=2 * CFG_RING.capacity)
    ring = TpuSpanStore(CFG_RING)
    paged = TpuSpanStore(CFG_PAGED)
    _drive(ring, spans)
    _drive(paged, spans)
    for i, (a, b) in enumerate(zip(ring.sketch_mirror.arrays(),
                                   paged.sketch_mirror.arrays())):
        np.testing.assert_array_equal(a, b, err_msg=f"mirror array {i}")


# ---------------------------------------------------------------------------
# Page reclaim under wrap (chain splice fuzz)
# ---------------------------------------------------------------------------


def test_page_reclaim_fuzz_invariants_and_liveness():
    """~4x-capacity skewed stream: free list + page table invariants
    hold after every batch, chains are spliced (never dangling), and
    live-trace queries return exactly the rows the device still holds
    — a subset of what was fed, never an invented or stale row."""
    spans, sizes = _skewed_stream(seed=97, total=4 * CFG_PAGED.capacity)
    fed_ids = {}
    for s in spans:
        fed_ids.setdefault(s.trace_id, set()).add(s.id)

    store = TpuSpanStore(CFG_PAGED)
    pl = store._planner
    for i in range(0, len(spans), 250):
        store.apply(spans[i:i + 250])
        st = pl.stats()
        assert st["pages_active"] + st["pages_free"] == pl.n_pages
        with pl._lock:
            # every chain entry points at a page still in its epoch
            # (reclaim must splice entries out, never leave them)
            for tid, ent in pl.traces.items():
                for (p, e) in ent.chain:
                    assert pl.page_epoch[p] == e, (tid, p, e)
                if not ent.overflowed:
                    assert ent.live == len(ent.chain), tid
            fills = [pl.page_fill[p] for p in range(pl.n_pages)
                     if pl.page_epoch[p] >= 0]
            assert all(0 <= f <= pl.R for f in fills)
    assert pl.stats()["page_reclaims"] > 0

    # Device/planner agreement: live rows on device == filled slots of
    # active pages (reclaim kills a page's rows in the claiming step).
    row_gid, trace_col = jax.device_get(
        (store.state.row_gid, store.state.trace_id))
    live = row_gid >= 0
    with pl._lock:
        planned = sum(pl.page_fill[p] for p in range(pl.n_pages)
                      if pl.page_epoch[p] >= 0)
    assert int(live.sum()) == planned

    # Query spot-check on surviving traces: what comes back is exactly
    # the device's live rows for that trace, drawn from the fed spans.
    with pl._lock:
        alive = [t for t, ent in pl.traces.items()
                 if not ent.overflowed][::5][:24]
    for tid in alive:
        got = store.get_spans_by_trace_ids([tid])[0]
        n_dev = int((live & (trace_col == tid)).sum())
        assert len(got) == n_dev, tid
        assert {s.id for s in got} <= fed_ids[tid], tid


def test_planner_rejects_bad_geometry():
    with pytest.raises(ValueError, match="power of two"):
        PagePlanner(CFG_RING._replace(layout="paged", page_rows=96))
    with pytest.raises(ValueError, match="multiple of page_rows"):
        PagePlanner(CFG_RING._replace(
            capacity=(1 << 10) + 8, layout="paged", page_rows=16))
    with pytest.raises(ValueError, match=">= 8 pages"):
        PagePlanner(CFG_RING._replace(layout="paged", page_rows=512))
    with pytest.raises(ValueError, match="layout"):
        PagePlanner(CFG_RING)


def test_sharded_store_rejects_paged_layout():
    from jax.sharding import Mesh

    from zipkin_tpu.parallel.shard import ShardedStore

    mesh = Mesh(np.array(jax.devices()[:1]), ("shard",))
    with pytest.raises(ValueError, match="single-device only"):
        ShardedStore(mesh, CFG_PAGED)


def test_paged_counters_and_census_budget():
    """counters() carries the allocator gauges only on the paged
    layout, and the fused-step lowering costs exactly the census
    table's +PAGED bump (zero silent growth)."""
    cfg_ring = CFG_RING._replace(rank_path="counting")
    cfg_paged = cfg_ring._replace(layout="paged", page_rows=128)
    spans, _ = _skewed_stream(seed=3, total=400)
    ring = TpuSpanStore(cfg_ring)
    paged = TpuSpanStore(cfg_paged)
    _drive(ring, spans)
    _drive(paged, spans)

    pc = paged.counters()
    assert pc["pages_active"] >= 1
    assert pc["pages_active"] + pc["pages_free"] == float(
        cfg_paged.n_pages)
    assert "page_reclaims_total" in pc
    assert "pages_active" not in ring.counters()

    ps, po, pg = expected_census("+PAGED")
    bs, bo, bg = expected_census()
    assert paged.step_census(256, 1024, 512) == {
        "scatter": ps, "sort": po, "gather": pg}
    assert ring.step_census(256, 1024, 512) == {
        "scatter": bs, "sort": bo, "gather": bg}


# ---------------------------------------------------------------------------
# Checkpoint: rev 18 roundtrip + pre-18 compat
# ---------------------------------------------------------------------------


def test_checkpoint_rev18_roundtrip_paged(tmp_path):
    """Save/load a WRAPPED paged store: device state bitwise, planner
    snapshot identical, queries answer the same, and post-restore
    ingest stays bitwise in lockstep with the uncheckpointed store
    (the planner must resume mid-epoch, not re-derive from zero)."""
    spans, sizes = _skewed_stream(seed=41, total=2 * CFG_PAGED.capacity)
    store = TpuSpanStore(CFG_PAGED)
    _drive(store, spans)

    path = str(tmp_path / "ckpt")
    checkpoint.save(store, path)
    rec = checkpoint.load(path)

    assert rec.config.layout == "paged"
    assert rec.config.page_rows == CFG_PAGED.page_rows
    assert states_bitwise_equal(store.state, rec.state)
    assert rec._planner.snapshot() == store._planner.snapshot()

    sample = sorted(sizes)[::9][:16]
    assert (store.get_spans_by_trace_ids(sample)
            == rec.get_spans_by_trace_ids(sample))

    # Post-restore writes: same tail stream → same claims → same bits.
    tail, _ = _skewed_stream(seed=43, total=300)
    _drive(store, tail)
    _drive(rec, tail)
    assert states_bitwise_equal(store.state, rec.state)
    assert rec._planner.stats() == store._planner.stats()


def test_pre18_snapshot_without_planner_meta_rebuilds(tmp_path):
    """A paged config pointed at a snapshot saved WITHOUT planner meta
    (the pre-18 shape) rebuilds the page table from the resident
    device columns — queries must answer exactly like the original."""
    spans, sizes = _skewed_stream(seed=53, total=2 * CFG_PAGED.capacity)
    store = TpuSpanStore(CFG_PAGED)
    _drive(store, spans)

    path = str(tmp_path / "ckpt")
    checkpoint.save(store, path)
    meta_file = os.path.join(path, "meta.json")
    with open(meta_file) as f:
        meta = json.load(f)
    assert meta["revision"] >= 18 and "paged" in meta
    del meta["paged"]
    meta["revision"] = 17
    with open(meta_file, "w") as f:
        json.dump(meta, f)

    rec = checkpoint.load(path)
    assert states_bitwise_equal(store.state, rec.state)
    st, rt = store._planner.stats(), rec._planner.stats()
    assert (st["pages_active"], st["pages_free"]) == (
        rt["pages_active"], rt["pages_free"])
    sample = sorted(sizes)[::11][:16]
    assert (store.get_spans_by_trace_ids(sample)
            == rec.get_spans_by_trace_ids(sample))


def test_pre18_ring_snapshot_still_loads(tmp_path):
    """Backward compat: a ring snapshot rewritten to the pre-18 meta
    shape (no layout knobs in config at all) restores through the
    revision-tolerant config checks as a ring store, bitwise."""
    spans, _ = _skewed_stream(seed=61, total=600)
    store = TpuSpanStore(CFG_RING)
    _drive(store, spans)

    path = str(tmp_path / "ckpt")
    checkpoint.save(store, path)
    meta_file = os.path.join(path, "meta.json")
    with open(meta_file) as f:
        meta = json.load(f)
    meta["revision"] = 17
    meta.pop("paged", None)
    for gone in ("layout", "page_rows", "page_max_chain"):
        meta["config"].pop(gone, None)
    with open(meta_file, "w") as f:
        json.dump(meta, f)

    rec = checkpoint.load(path)
    assert rec.config.layout == "ring"
    assert rec._planner is None
    assert states_bitwise_equal(store.state, rec.state)


# ---------------------------------------------------------------------------
# WAL: deterministic, bitwise replay of the paged plan stream
# ---------------------------------------------------------------------------


def test_wal_replay_paged_is_bitwise(tmp_path):
    """Replaying the journal into a FRESH paged store re-derives the
    exact claim sequence: device state AND planner page table (free
    list, epochs, chains, touch stamps) come back bit-identical."""
    spans, _ = _skewed_stream(seed=71, total=2 * CFG_PAGED.capacity)
    store = TpuSpanStore(CFG_PAGED)
    wal = WriteAheadLog(str(tmp_path / "wal"), fsync="off")
    store.attach_wal(wal)
    _drive(store, spans)
    wal.sync()
    assert store._planner.stats()["page_reclaims"] > 0

    fresh = TpuSpanStore(CFG_PAGED)
    stats = replay_into(fresh, wal, from_seq=0)
    assert stats["replayed_records"] == wal.last_seq
    assert states_bitwise_equal(store.state, fresh.state)
    assert fresh._planner.snapshot() == store._planner.snapshot()
    wal.close()


def test_recover_checkpoint_plus_tail_replays_recorded_plans(tmp_path):
    """Mid-stream checkpoint + tail replay (the crash shape): plans at
    seq <= the snapshot's frontier replay from the recorded memo, the
    tail re-derives — recovery lands bitwise on the uncrashed oracle,
    wrap and reclaims included, and keeps ingesting identically."""
    spans, _ = _skewed_stream(seed=83, total=2 * CFG_PAGED.capacity)
    # Cut on a _drive batch boundary: the claim plan is a function of
    # the CHUNK stream, so oracle and crashed store must batch alike.
    half = (len(spans) // 2 // 200) * 200

    oracle = TpuSpanStore(CFG_PAGED)
    _drive(oracle, spans)

    store = TpuSpanStore(CFG_PAGED)
    wal = WriteAheadLog(str(tmp_path / "wal"), fsync="off")
    store.attach_wal(wal)
    _drive(store, spans[:half])
    checkpoint.save(store, str(tmp_path / "ckpt"))
    _drive(store, spans[half:])
    wal.sync()
    del store  # crash: HBM gone, snapshot + log survive

    wal2 = WriteAheadLog(str(tmp_path / "wal"), fsync="off")
    rec, rstats = recover(str(tmp_path / "ckpt"), wal2)
    assert rstats["replayed_records"] > 0
    assert states_bitwise_equal(oracle.state, rec.state)
    assert rec._planner.stats() == oracle._planner.stats()

    tail, _ = _skewed_stream(seed=89, total=250)
    _drive(oracle, tail)
    _drive(rec, tail)
    assert states_bitwise_equal(oracle.state, rec.state)
    wal2.close()
